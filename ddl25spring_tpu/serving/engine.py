"""Step-level serving engine: prefill()/decode_step() over a fixed slot axis.

`models/generate.py` fuses prefill + the whole decode horizon into one
compiled scan — perfect for a bench, useless for a server, where the batch
composition changes at every token boundary. This engine refactors the same
math into TWO reusable compiled programs over a fixed slot axis ``[S]``:

- ``prefill_chunk``: one slot's prompt chunk ``[1, Tc]`` through the model,
  writing K/V into the slot's pool blocks; the FINAL chunk also samples the
  first token (TTFT). Chunking lets a long prompt interleave with in-flight
  decode instead of stalling it — the scheduler advances one chunk per
  token boundary.
- ``decode_step``: one token for ALL slots ``[S]`` at once — per-slot
  position, RNG key, temperature and active-mask ride in the slot state, so
  admissions/retirements between steps never recompile anything.
- ``verify_step`` (speculation armed, serving/speculate.py): the decode
  step widened to a ``k+1``-position window per slot — one dispatch
  scores a draft's whole proposal, so decode throughput scales with the
  acceptance rate instead of paying one dispatch per token.

What a program runs is read from the model's description
(``config.describe``): a ``LlamaConfig`` model's dense block over a pool of
K and V per head (``_block_paged``; everything below about bitwise parity is
of this path, and its lowered text is the parent's), or, for a
``ModelDescription`` with latent attention and expert layers, one scan a run
of layers of one kind over a pool of one latent row a position a layer
(``_forward_described``; ``models/latent.py``, ``models/experts.py``, imported
only then; checked against a float32 reference, tests/test_latent_experts.py).
On a TPU that model's prefill chunk attends through a kernel over the
expanded rows that stops at the chunk's live keys (``ops/chunk_attention.py``;
``chunk_attention_path`` picks it as ``paged_attention_path`` picks the decode
kernel below; tests/test_chunk_attention.py holds it to the XLA form).
A ``ModelDescription`` of ``parallel`` layers (a state-space mixer beside
grouped-query attention in every block, ``models/state_space.py``) goes
through ``_forward_parallel``: the same pool of K and V, of the key/value
heads only, and beside it in the same donated tree a state store of one row a
SLOT (``kvcache.init_state``), which a prefill chunk carries on for its slot
and a decode step for every decoding slot (tests/test_state_space.py).
The engine keeps ONE copy of each weight, in the layout its programs read.

Each is compiled exactly once per engine (static shapes: every dispatch
passes the block table at full width). The stacked pool is donated and
is the layer scan's carry, written and gathered by (layer, block, offset),
so argument, loop state and result are one buffer and no program slices a
layer's pool out of it or writes one back. All are built from the same
building blocks as ``generate`` — ``_fuse_blocks``, ``llama.embed/head``,
the fp32-softmax attention layout of ``_attend_cached`` — deliberately
op-for-op, because the acceptance bar is BITWISE: a request decoded here,
at any slot, in any company, must emit exactly the tokens ``generate()``
emits for it alone (tests/test_generate.py, tests/test_serving.py).

That bar is the XLA path's, in float32, on the CPU, where the tier-1 tests
hold it. On a TPU the decode program's attention (``T == 1``) is a kernel
that reads each slot's live blocks in the pool as they lie
(``ops/paged_attention.py``; ``paged_attention_path`` picks it where the
program is traced, from the backend and the shapes): no gather, no padded
positions, an online softmax in another order of sums. It is held to the
XLA path by tolerance in tier-1 (tests/test_paged_attention.py, interpret
mode) and to the float32 reference by ``served_logit_gap`` on the chip
(PERF.md), where in bf16 the bitwise bar has not held since PR 21.
``prefill_chunk`` and ``verify_step`` (more than one query row a slot) keep
the gather and ``_attend_paged`` on every backend.

The bitwise-parity constraints that shaped the code:
- Every op is row-independent (norms, matmuls, softmax-per-row, per-slot
  RNG), so batch company cannot leak between slots.
- The gathered cache is padded to ``paged.max_seq_len`` and masked by
  absolute position; masked garbage contributes exact zeros through
  softmax (``exp(-inf) = 0``), same as ``generate``'s unwritten tail —
  parity tests run ``generate(max_len=paged.max_seq_len)`` so both sides
  reduce over identically-shaped score rows.
- Per-slot sampling keeps ``generate``'s exact RNG discipline: split the
  slot key every step, sample from the sub-key — so equal seeds give equal
  streams. Temperature is a traced per-slot scalar (greedy selected by a
  ``where``, both branches computed); top_k/top_p stay engine-static, the
  same filters ``_sample`` applies.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..config import LlamaConfig, ModelDescription, describe
from .. import nn
from ..models import generate, llama
from ..telemetry.trace import Spans
from .kvcache import (TRASH_BLOCK, BlockAllocator, PagedKVConfig, blocks_for,
                      init_pool, init_state, state_bytes_per_slot)


def check_swappable(old, new) -> None:
    """Raise unless ``new`` matches ``old`` leaf-for-leaf in tree
    structure, shape and dtype — the equal-tree contract every weight
    hot-swap must satisfy (a mismatch would silently retrace the two
    compiled programs). Shared by ``Engine.swap_params`` (per-engine
    enforcement) and ``ServingFleet.publish`` (fail a bad publish
    ATOMICALLY, before any engine pops from the rollout)."""
    o_leaves, o_def = jax.tree_util.tree_flatten(old)
    n_leaves, n_def = jax.tree_util.tree_flatten(new)
    if o_def != n_def:
        raise ValueError("swap_params: new params tree structure does "
                         "not match the serving engine's")
    for o, n in zip(o_leaves, n_leaves):
        if o.shape != n.shape or o.dtype != n.dtype:
            raise ValueError(
                f"swap_params: leaf mismatch {n.shape}/{n.dtype} vs "
                f"engine's {o.shape}/{o.dtype} — a shape change would "
                "retrace the engine's two compiled programs")


class LeafSpec:
    """What the engine remembers of a leaf of the tree it was booted with,
    once it holds the weights in its own layout: enough for
    ``check_swappable`` and ``_match_placement``."""
    __slots__ = ("shape", "dtype", "committed", "sharding")

    def __init__(self, leaf):
        self.shape, self.dtype = leaf.shape, leaf.dtype
        self.committed = bool(getattr(leaf, "committed", False))
        self.sharding = getattr(leaf, "sharding", None)


def _match_placement(new, old):
    """Return ``new`` placed EXACTLY like ``old`` (device + committed-ness,
    leaf by leaf; ``old`` may be a tree of ``LeafSpec``). The jit cache
    key includes argument placement, so a
    hot-swapped tree must be indistinguishable in placement from the boot
    params or both compiled programs would silently retrace — and a tree
    restored from a checkpoint arrives device_put-COMMITTED while
    ``init_llama``'s boot params are uncommitted. Shedding a commitment
    requires a host bounce (there is no uncommit-in-place); that is one
    params-sized copy per publish, trivial next to the disk read that
    produced the tree."""
    def fix(n, o):
        if not isinstance(n, jax.Array) or not hasattr(o, "sharding"):
            return n
        nc = bool(getattr(n, "committed", False))
        oc = bool(getattr(o, "committed", False))
        if oc:
            return n if nc and n.sharding == o.sharding \
                else jax.device_put(n, o.sharding)
        return n if not nc else jnp.asarray(np.asarray(n))
    return jax.tree.map(fix, new, old)


# ------------------------------------------------------------- paged forward

def _attend_paged(q: jnp.ndarray, ck: jnp.ndarray, cv: jnp.ndarray,
                  q_positions: jnp.ndarray) -> jnp.ndarray:
    """``generate._attend_cached`` with a PER-SLOT position mask: q
    [S, Tq, H, Dh] over the gathered cache [S, Tmax, H, Dh], masked to
    ``kpos <= q_position`` per (slot, query-row). Identical layout and op
    sequence (fp32 softmax, heads folded into batch) so per-row numerics
    match the contiguous-cache path bitwise."""
    b, tq, h, dh = q.shape
    tmax = ck.shape[1]
    scale = 1.0 / math.sqrt(dh)
    qm = q.transpose(0, 2, 1, 3).reshape(b * h, tq, dh)
    km = ck.transpose(0, 2, 1, 3).reshape(b * h, tmax, dh).astype(q.dtype)
    vm = cv.transpose(0, 2, 1, 3).reshape(b * h, tmax, dh).astype(q.dtype)
    scores = lax.dot_general(qm, km, (((2,), (2,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32) * scale
    qpos = jnp.broadcast_to(q_positions[:, None, :], (b, h, tq))
    mask = qpos.reshape(b * h, tq)[:, :, None] >= jnp.arange(tmax)[None, None, :]
    scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = lax.dot_general(probs, vm, (((2,), (1,)), ((0,), (0,))))
    return out.reshape(b, h, tq, dh).transpose(0, 2, 1, 3)


def paged_attention_path(t: int, h: int, dh: int, kv_dtype,
                         block_len: int = 1) -> dict:
    """The attention a ``_block_paged`` call of ``t`` query rows a slot over
    a pool of ``h`` heads of ``dh`` in ``kv_dtype`` (``block_len``: given
    for grouped key/value heads, ``ops.paged_attention.supported``) traces
    to on this backend: ``{"impl": "pallas" | "xla", "interpret": bool | None}``, as
    ``llama.attention_path`` says it for the flash kernel. The kernel
    (``ops/paged_attention.py``) is the decode program's, ``t == 1``, on a
    TPU, where the pool's blocks are whole tiles; a chunk of queries, a
    window of ``k + 1`` rows and every program on another backend keep the
    gather and ``_attend_paged``. Looked up where the program is traced and
    where the engine is built (what ``gathered_positions`` counts): a test
    that wants the kernel in the program replaces this function."""
    xla = {"impl": "xla", "interpret": None}
    if t != 1 or jax.default_backend() != "tpu":
        return xla
    from ..ops import paged_attention as pa     # Pallas: only where it runs
    if not pa.supported(h, dh, kv_dtype, block_len):
        return xla
    return {"impl": "pallas", "interpret": False}


def _apply_rope_slots(x: jnp.ndarray, cos: jnp.ndarray,
                      sin: jnp.ndarray) -> jnp.ndarray:
    """``llama.apply_rope`` with per-slot tables: cos/sin [S, T, half]
    instead of the shared [T, half] (slots sit at different absolute
    positions). Same rotation arithmetic, elementwise."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[:, :, None, :].astype(x.dtype)
    s = sin[:, :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


def _block_paged(block: dict, layer: jnp.ndarray, pk: jnp.ndarray,
                 pv: jnp.ndarray, x: jnp.ndarray, positions: jnp.ndarray,
                 tables: jnp.ndarray, wblk: jnp.ndarray, woff: jnp.ndarray,
                 cfg: LlamaConfig, attend=None):
    """One pre-fused block, layer number ``layer``, over x [S, T, D] at
    per-slot absolute ``positions`` [S, T]. ``pk``/``pv`` are the WHOLE
    stacked pool [L, num_blocks, block_len, H, Dh]: this call's K/V is
    scattered into it at (``layer``, ``wblk``, ``woff``) [S, T] and each
    slot's block table is gathered from it at (``layer``, ``tables``); no
    operation produces or consumes one layer's pool. The paged twin of
    ``generate._block_with_cache``; the scatter/gather replaces its
    dynamic_update_slice/full-cache read, the math around them is
    identical. With ``attend`` (``_kernel_attention``: a decode step on a
    TPU) there is no gather: ``attend(q, pk, pv, layer)`` reads the live
    blocks in the pool as it lies."""
    s, t, d = x.shape
    dh = cfg.head_dim
    # The named scopes cost nothing at run time and change no number: they
    # are how a device trace tells the block's parts apart (``paged.*`` is
    # what the paged-attention metrics time; docs/COMPONENTS.md).
    with jax.named_scope("qkv"):
        xn = nn.rmsnorm(block["attn_norm"], x, eps=cfg.norm_eps)
        qkv = xn @ block["w_qkv"].astype(x.dtype)
        dl = qkv.shape[-1] // 3
        h_local = dl // dh
        q = qkv[..., :dl].reshape(s, t, h_local, dh)
        k = qkv[..., dl:2 * dl].reshape(s, t, h_local, dh)
        v = qkv[..., 2 * dl:].reshape(s, t, h_local, dh)
        cos, sin = llama.rope_angles(positions.reshape(-1), dh,
                                     cfg.rope_theta)
        cos = cos.reshape(s, t, -1)
        sin = sin.reshape(s, t, -1)
        q = _apply_rope_slots(q, cos, sin)
        k = _apply_rope_slots(k, cos, sin)   # cached K is stored post-RoPE
    # Per-token scatter into the stacked pool. Distinct (block, offset)
    # targets are guaranteed by block ownership; only TRASH_BLOCK collides
    # (inactive slots, padded tails; each layer has its own) and its
    # contents are never read un-masked.
    with jax.named_scope("paged.write"):
        pk = pk.at[layer, wblk, woff].set(k.astype(pk.dtype))
        pv = pv.at[layer, wblk, woff].set(v.astype(pv.dtype))
    if attend is not None:
        with jax.named_scope("paged.attend"):
            out = attend(q, pk, pv, layer)
    else:
        with jax.named_scope("paged.gather"):
            ck = pk[layer, tables].reshape(s, -1, h_local, dh)  # [S, Tmax, H, Dh]
            cv = pv[layer, tables].reshape(s, -1, h_local, dh)
        with jax.named_scope("paged.attend"):
            out = _attend_paged(q, ck, cv, positions)
    with jax.named_scope("attn_out"):
        x = x + out.reshape(s, t, h_local * dh) @ block["wo"].astype(x.dtype)
    with jax.named_scope("mlp"):
        xn = nn.rmsnorm(block["mlp_norm"], x, eps=cfg.norm_eps)
        gu = xn @ block["w_gu"].astype(x.dtype)
        f = gu.shape[-1] // 2
        x = x + (jax.nn.silu(gu[..., :f]) * gu[..., f:]) @ block["w_down"].astype(x.dtype)
    return x, pk, pv


def _kernel_attention(pool: dict, tables: jnp.ndarray,
                      positions: jnp.ndarray, valid: Optional[jnp.ndarray],
                      group: int = 1):
    """``_block_paged``'s ``attend`` where ``paged_attention_path`` names
    the kernel for this program, else None. What the kernel fetches is the
    same for every layer, so it is reckoned here, once, outside the layer
    scan: a slot attends to the position it has just written and all before
    it; one that is not decoding (``valid`` [S, 1] False: it wrote to
    trash) reads nothing. ``group`` query heads read each of the pool's
    key/value heads (``_block_parallel``; the path function is then asked
    with the block's length, ``ops.paged_attention.supported``)."""
    pk = pool["k"]
    grouped = () if group == 1 else (pk.shape[2],)
    path = paged_attention_path(positions.shape[1], *pk.shape[3:], pk.dtype,
                                *grouped)
    if path["impl"] != "pallas":
        return None
    from ..ops import paged_attention as pa
    lengths = positions[:, 0] + 1
    if valid is not None:
        lengths = jnp.where(valid[:, 0], lengths, 0)
    walk = pa.plan(tables, lengths, pk.shape[2], pk.shape[3], group)

    def attend(q, pk, pv, layer):
        return pa.paged_attention(q[:, 0], pk, pv, layer, walk,
                                  interpret=path["interpret"])[:, None]
    return attend


def chunk_attention_path(t: int, k_len: int, desc: ModelDescription) -> dict:
    """The attention a ``_block_latent`` call of ``t`` query rows a slot
    over ``k_len`` gathered positions traces to on this backend, as
    ``paged_attention_path`` says it for the decode program of a
    ``LlamaConfig`` model. The kernel (``ops/chunk_attention.py``) is the
    prefill chunk's on a TPU: where ``t`` rows expand the latent rows to
    per-head K and V (``latent.expand_pays``) and head sizes, blocks and
    dtype are whole tiles. A decode step (folded attention) and every
    program on another backend keep ``models/latent.py``'s XLA forms.
    Looked up where the program is traced and where the engine is built
    (what ``attended_positions`` counts): a test that wants the kernel in
    the program replaces this function."""
    from ..models import latent
    att = desc.attention
    xla = {"impl": "xla", "interpret": None}
    if (jax.default_backend() != "tpu"
            or not latent.expand_pays(t, att, desc.num_heads)):
        return xla
    from ..ops import chunk_attention as ca     # Pallas: only where it runs
    if not ca.supported(t, k_len, att.nope_dim, att.rope_dim, att.v_dim,
                        desc.dtype):
        return xla
    return {"impl": "pallas", "interpret": False}


def _chunk_attention(desc: ModelDescription, k_len: int,
                     positions: jnp.ndarray, valid: Optional[jnp.ndarray]):
    """``latent.attend``'s ``fused`` where ``chunk_attention_path`` names
    the kernel for this program, else None. The live length is the same for
    every layer, so it is reckoned here, once, outside the layer scans: the
    keys a slot's chunk may see end with its last real token's own
    (``valid`` [S, T] marks the real ones)."""
    path = chunk_attention_path(positions.shape[1], k_len, desc)
    if path["impl"] != "pallas":
        return None
    from ..models import latent
    from ..ops import chunk_attention as ca
    att = desc.attention
    live = jnp.max(positions if valid is None
                   else jnp.where(valid, positions, -1), axis=1) + 1

    def fused(q, kv, k_rope, q_positions):
        return ca.chunk_attention(
            q, kv, k_rope, q_positions, live, nope_dim=att.nope_dim,
            scale=latent.softmax_scale(att), interpret=path["interpret"])
    return fused


def _block_latent(block: dict, kind: str, layer: jnp.ndarray,
                  pc: jnp.ndarray, x: jnp.ndarray, positions: jnp.ndarray,
                  tables: jnp.ndarray, wblk: jnp.ndarray, woff: jnp.ndarray,
                  valid: jnp.ndarray, desc: ModelDescription,
                  group_offset=None, fused=None):
    """One layer of a latent-attention model (``models/latent.py``), layer
    number ``layer`` of kind ``kind``, over x [S, T, D]: the paged twin of
    ``_block_paged`` for a pool ``pc`` [L, num_blocks, block_len, row_stride]
    of ONE row a position a layer. The row is written after its norm and
    rotation; attention reads the gathered rows as they lie, folded into
    latent space for a decode step, expanded for a prefill chunk
    (``latent.attend`` picks by T; ``fused``: ``_chunk_attention``'s
    kernel over the expanded rows, or None). Returns (x, pc, routing stats
    of an expert layer or None)."""
    from ..models import latent

    s, t, _ = x.shape
    with jax.named_scope("q_proj"):
        xn = nn.rmsnorm(block["attn_norm"], x, eps=desc.norm_eps)
        cos, sin = latent.rope_tables(positions, desc.attention,
                                      desc.rope_theta)
        q = latent.queries(block, xn, cos, sin, desc)
    with jax.named_scope("kv_proj"):
        row = latent.latent_row(block, xn, cos, sin, desc)
    with jax.named_scope("latent.write"):
        # whole vectors of lanes a row, the rest zero (kvcache.row_stride)
        row = jnp.pad(row, ((0, 0), (0, 0), (0, pc.shape[-1] - row.shape[-1])))
        pc = pc.at[layer, wblk, woff].set(row.astype(pc.dtype))
    with jax.named_scope("latent.gather"):
        rows = pc[layer, tables].reshape(s, -1, pc.shape[-1])
    with jax.named_scope("latent.attend"):
        out = latent.attend(block["w_kvb"], q, rows, positions, desc, fused)
    with jax.named_scope("attn_out"):
        x = x + out.reshape(s, t, -1) @ block["w_o"].astype(x.dtype)
    x, stats = latent.second_half(block, kind, x, desc, valid, group_offset)
    return x, pc, stats


def _forward_described(head: dict, runs: tuple, tokens: jnp.ndarray,
                       pool: dict, tables, positions, wblk, woff, valid,
                       desc: ModelDescription):
    """``_forward_paged`` for a model whose layers differ: one lax.scan a
    run of layers of one kind (``ModelDescription.runs``; a leading dense
    layer, then the expert layers), the hidden state and the whole pool
    the carry of each, the routing stats of an expert run its stacked
    result [layers of the run, 3]."""
    with jax.named_scope("embed"):
        h = head["embed"][tokens].astype(jnp.dtype(desc.dtype))
    pc, stats = pool["c"], []
    fused = _chunk_attention(desc, tables.shape[1] * pc.shape[2], positions,
                             valid)
    for (kind, start, count), blocks in zip(desc.runs(), runs):
        layers = start + jnp.arange(count, dtype=jnp.int32)
        whole = {}
        if kind == "experts":
            # The routed experts stay out of the scanned tree, the run's
            # layers side by side as one stack of groups, and each layer
            # names its own by offset (``experts.expert_layer``).
            whole = {k: blocks[k].reshape((-1,) + blocks[k].shape[2:])
                     for k in ("we_gu", "we_down")}
            blocks = {k: v for k, v in blocks.items() if k not in whole}
        held = desc.experts.held_count if whole else 0

        def body(carry, layer_block):       # traced here, by this scan
            x, pc = carry
            layer, block = layer_block
            x, pc, st = _block_latent(
                dict(block, **whole), kind, layer, pc, x, positions, tables,
                wblk, woff, valid, desc,
                (layer - start) * held if whole else None, fused)
            return (x, pc), st

        with jax.named_scope("layers"):
            (h, pc), st = lax.scan(body, (h, pc), (layers, blocks))
        if st is not None:
            stats.append(st)
    return h, {"c": pc}, (jnp.concatenate(stats) if stats else None)


def _attend_grouped(q: jnp.ndarray, ck: jnp.ndarray, cv: jnp.ndarray,
                    q_positions: jnp.ndarray) -> jnp.ndarray:
    """``_attend_paged`` for fewer key/value heads than query heads: q
    [S, Tq, Hq, Dh] over the gathered cache [S, Tmax, H, Dh], query head
    ``i`` reading key/value head ``i // (Hq / H)``. The queries of a group
    are laid side by side as rows of their key/value head, so the cache is
    read once a key/value head and never repeated."""
    s, tq, hq, dh = q.shape
    h = ck.shape[2]
    g = hq // h
    rows = q.reshape(s, tq, h, g, dh).transpose(0, 3, 1, 2, 4).reshape(
        s, g * tq, h, dh)
    out = _attend_paged(rows, ck, cv, jnp.tile(q_positions, (1, g)))
    return out.reshape(s, g, tq, h, dh).transpose(0, 2, 3, 1, 4).reshape(
        s, tq, hq, dh)


def _block_parallel(block: dict, layer: jnp.ndarray, pool: dict,
                    x: jnp.ndarray, positions: jnp.ndarray,
                    tables: jnp.ndarray, wblk: jnp.ndarray,
                    woff: jnp.ndarray, n_valid: jnp.ndarray, slot,
                    desc: ModelDescription, attend=None):
    """One layer of a model whose two mixers stand side by side
    (``models/state_space.py``), layer number ``layer``, over x [S, T, D]:
    ``u = norm(x)``; ``x + ssm_out SSM(u) + attention_out Attn(attention_in
    u)``, then the SwiGLU with its two multipliers. Attention is
    ``_block_paged``'s over a pool of ``kv_heads`` heads of ``head_dim``
    (the same scopes; ``attend``: ``_kernel_attention``'s, told the group). The
    mixer reads and writes the second kind of cache in place
    (``kvcache.init_state``): ``pool["s"]`` and ``pool["tail"]`` WHOLE, of
    which this call touches layer ``layer`` of slot ``slot`` (a prefill
    chunk, S == 1; position 0 is a request's first, and starts from zeros
    whatever the slot held) or of every slot (``slot`` None: a decode
    step). ``n_valid`` [S] real positions: a slot with none keeps state and
    tail."""
    from ..models import state_space

    m, mx = desc.multipliers, desc.mixer
    s, t, d = x.shape
    hq, h, dh = desc.num_heads, desc.num_kv_heads, desc.head_dim
    pk, pv, st, tl = pool["k"], pool["v"], pool["s"], pool["tail"]
    with jax.named_scope("qkv"):
        u = nn.rmsnorm(block["in_norm"], x, eps=desc.norm_eps)
        qkv = ((u * jnp.asarray(m.attention_in, u.dtype))
               @ block["w_qkv"].astype(x.dtype))
        q = qkv[..., :hq * dh].reshape(s, t, hq, dh)
        k = (qkv[..., hq * dh:(hq + h) * dh]
             * jnp.asarray(m.key, u.dtype)).reshape(s, t, h, dh)
        v = qkv[..., (hq + h) * dh:].reshape(s, t, h, dh)
        cos, sin = llama.rope_angles(positions.reshape(-1), dh,
                                     desc.rope_theta)
        cos = cos.reshape(s, t, -1)
        sin = sin.reshape(s, t, -1)
        q = _apply_rope_slots(q, cos, sin)
        k = _apply_rope_slots(k, cos, sin)
    with jax.named_scope("paged.write"):
        pk = pk.at[layer, wblk, woff].set(k.astype(pk.dtype))
        pv = pv.at[layer, wblk, woff].set(v.astype(pv.dtype))
    if attend is not None:
        with jax.named_scope("paged.attend"):
            out = attend(q, pk, pv, layer)
    else:
        with jax.named_scope("paged.gather"):
            ck = pk[layer, tables].reshape(s, -1, h, dh)
            cv = pv[layer, tables].reshape(s, -1, h, dh)
        with jax.named_scope("paged.attend"):
            out = _attend_grouped(q, ck, cv, positions)
    with jax.named_scope("attn_out"):
        att = (out.reshape(s, t, hq * dh) @ block["w_o"].astype(x.dtype)
               ) * jnp.asarray(m.attention_out, x.dtype)
    # this call's rows of the state store: every slot's (a decode step), or
    # one slot's, from zeros where the chunk is its request's first
    if slot is None:
        take = lambda a: a[layer]                           # noqa: E731
        put = lambda a, rows: a.at[layer].set(rows)         # noqa: E731
    else:
        fresh = positions[0, 0] == 0

        def take(a):
            rows = lax.dynamic_slice(
                a, (layer, slot) + (0,) * (a.ndim - 2), (1, 1) + a.shape[2:])
            return jnp.where(fresh, jnp.zeros_like(rows[0]), rows[0])

        def put(a, rows):
            return lax.dynamic_update_slice(
                a, rows[None], (layer, slot) + (0,) * (a.ndim - 2))
    y, s1, t1 = state_space.mixer(block, u, take(st), take(tl), n_valid, desc)
    with jax.named_scope("ssm.scan"):
        st = put(st, s1)
    with jax.named_scope("ssm.conv"):
        tl = put(tl, t1)
    with jax.named_scope("ssm.out"):
        x = x + y * jnp.asarray(m.ssm_out, x.dtype) + att
    with jax.named_scope("mlp"):
        xn = nn.rmsnorm(block["ff_norm"], x, eps=desc.norm_eps)
        gu = xn @ block["w_gu"].astype(x.dtype)
        f = gu.shape[-1] // 2
        gate = jax.nn.silu(gu[..., :f] * jnp.asarray(m.mlp[0], x.dtype))
        x = x + ((gate * gu[..., f:]) @ block["w_down"].astype(x.dtype)
                 ) * jnp.asarray(m.mlp[1], x.dtype)
    return x, {"k": pk, "v": pv, "s": st, "tail": tl}


def _forward_parallel(head: dict, runs: tuple, tokens: jnp.ndarray,
                      pool: dict, tables, positions, wblk, woff, valid, slot,
                      desc: ModelDescription):
    """``_forward_paged`` for a model of ``parallel`` layers: one lax.scan
    over (layer number, block) with the hidden state, the whole pool and the
    whole state store as its carry; under the programs' donation each of
    them is one buffer, argument, loop state and result."""
    with jax.named_scope("embed"):
        h = (head["embed"][tokens].astype(jnp.dtype(desc.dtype))
             * jnp.asarray(desc.multipliers.embedding, jnp.dtype(desc.dtype)))
    n_valid = jnp.sum(valid, axis=1).astype(jnp.int32)
    attend = _kernel_attention(pool, tables, positions, valid,
                               desc.num_heads // desc.num_kv_heads)
    layers = jnp.arange(desc.n_layers, dtype=jnp.int32)

    def body(carry, layer_block):
        x, pool = carry
        layer, block = layer_block
        return _block_parallel(block, layer, pool, x, positions, tables, wblk,
                               woff, n_valid, slot, desc, attend), None

    with jax.named_scope("layers"):
        (h, pool), _ = lax.scan(body, (h, pool), (layers, runs[0]))
    return h, pool, None


def _head(params: dict, h: jnp.ndarray, cfg) -> jnp.ndarray:
    """``llama.head`` times the model's ``lm_head_multiplier``, where it has
    one."""
    logits = llama.head(params, h, cfg)
    m = describe(cfg).multipliers
    if m is None:
        return logits
    with jax.named_scope("head"):
        return logits * jnp.float32(m.lm_head)


def _forward_paged(params: dict, fused_blocks, tokens: jnp.ndarray,
                   pool: dict, tables: jnp.ndarray, positions: jnp.ndarray,
                   wblk: jnp.ndarray, woff: jnp.ndarray, cfg,
                   valid: Optional[jnp.ndarray] = None, slot=None):
    """tokens [S, T] at per-slot absolute ``positions`` [S, T] → (hidden
    [S, T, D], updated pool, routing stats or None). Dispatches on the
    model's description: a model of other layer kinds than ``LlamaConfig``
    states goes through ``_forward_described`` (``valid`` [S, T] marks its
    real tokens for the expert layers) or, with a state-space mixer,
    ``_forward_parallel`` (``slot``: the one slot a prefill chunk is of).
    Else one lax.scan over (layer
    number, fused block) with the hidden state AND the whole stacked pool
    as its carry: under the programs' donation of the pool, argument, loop
    state and result are one buffer, written and gathered in place by
    (layer, block, offset). (``generate._forward_fused`` scans its cache as
    stacked inputs and outputs; a pool of gigabytes cannot afford the slice
    out and the write back that costs, every layer of every run.)"""
    desc = describe(cfg)
    if desc.mixer is not None:
        return _forward_parallel(params, fused_blocks, tokens, pool, tables,
                                 positions, wblk, woff, valid, slot, desc)
    if not desc.plain:
        return _forward_described(params, fused_blocks, tokens, pool, tables,
                                  positions, wblk, woff, valid, desc)
    h = llama.embed(params, tokens, cfg)
    layers = jnp.arange(pool["k"].shape[0], dtype=jnp.int32)
    attend = _kernel_attention(pool, tables, positions, valid)

    def body(carry, layer_block):
        x, pk, pv = carry
        layer, block = layer_block
        return _block_paged(block, layer, pk, pv, x, positions, tables,
                            wblk, woff, cfg, attend), None

    # ``layers`` names the scan. The block's operations read
    # ``.../layers/while/body/closed_call/<scope>/<op>``; what stands under
    # ``layers`` with no inner scope (what the compiler hoists out of one)
    # is what ``decode_unscoped_ms.serve`` times.
    with jax.named_scope("layers"):
        (h, pk, pv), _ = lax.scan(body, (h, pool["k"], pool["v"]),
                                  (layers, fused_blocks))
    return h, {"k": pk, "v": pv}, None


def _sample_slot(key, logits: jnp.ndarray, temperature: jnp.ndarray,
                 top_k: Optional[int], top_p: Optional[float]) -> jnp.ndarray:
    """``generate._sample`` with a TRACED per-slot temperature: logits
    [1, V] → token [1]. Greedy (t == 0) is a ``where``-select over both
    branches instead of Python control flow, so one compile serves any
    per-slot mix; the sampled branch applies the SAME ``filter_logits``
    and ``categorical`` ops as ``generate`` (one filter implementation —
    the bitwise-parity bar depends on it)."""
    greedy = jnp.argmax(logits, axis=-1)
    safe_t = jnp.where(temperature > 0, temperature, 1.0)
    scaled = generate.filter_logits(logits / safe_t, top_k, top_p)
    sampled = jax.random.categorical(key, scaled, axis=-1)
    return jnp.where(temperature > 0, sampled, greedy)


# ------------------------------------------------------------ compiled steps

def make_prefill_chunk(cfg: LlamaConfig, paged: PagedKVConfig,
                       chunk_len: int, top_k: Optional[int],
                       top_p: Optional[float]):
    """One compiled program: one slot's prompt chunk [chunk_len] through the
    model, K/V scattered into the slot's blocks. Also computes the
    next-token sample from the chunk's last VALID row — the host uses it
    only for the final chunk (``generate`` splits its key exactly once
    after prefill, so intermediate chunks must not consume randomness:
    the caller passes the key only when ``is_final``).

    ``write_from`` (CoW prefix sharing, kvcache.py): positions below it
    route their K/V writes to the trash block — the slot READS those
    positions from blocks it shares with an earlier identical prefix, so
    re-writing them would scribble on another request's read-only blocks.
    The recomputed values are bitwise the shared ones (same tokens, same
    positions, same weights), so discarding them changes nothing. 0 (the
    non-sharing case) writes everything, byte-for-byte the old program.

    ``slot`` (a model with a state-space mixer only): the slot whose rows
    of the state store, donated with the pool, the chunk reads and writes."""
    bl, mb = paged.block_len, paged.max_blocks_per_seq

    @partial(jax.jit, donate_argnums=(0,))
    def prefill_chunk(pool: dict, params: dict, fused: dict,
                      table_row: jnp.ndarray, tokens: jnp.ndarray,
                      start: jnp.ndarray, n_valid: jnp.ndarray,
                      write_from: jnp.ndarray,
                      key: jnp.ndarray, temperature: jnp.ndarray, slot=None):
        start = jnp.asarray(start, jnp.int32)
        pos = start + jnp.arange(chunk_len, dtype=jnp.int32)       # [Tc]
        valid = jnp.logical_and(jnp.arange(chunk_len) < n_valid,
                                pos >= write_from)
        blk_idx = jnp.minimum(pos // bl, mb - 1)
        wblk = jnp.where(valid, table_row[blk_idx], TRASH_BLOCK)
        woff = pos % bl
        h, pool, stats = _forward_paged(
            params, fused, tokens[None], pool, table_row[None], pos[None],
            wblk[None], woff[None], cfg,
            (jnp.arange(chunk_len) < n_valid)[None], slot)
        # Logits of the last valid row only — the [1, 1, D] head matmul
        # ``generate`` performs (never the full [Tc, V] logits).
        last = jnp.take_along_axis(
            h, (n_valid - 1).reshape(1, 1, 1).astype(jnp.int32), axis=1)
        logits = _head(params, last, cfg)[:, 0, :]                 # [1, V]
        with jax.named_scope("sample"):
            key, sub = jax.random.split(key)
            tok = _sample_slot(sub, logits, temperature, top_k, top_p)
        if stats is not None:       # a model with expert layers
            return pool, tok[0], key, stats
        return pool, tok[0], key

    return prefill_chunk


def make_decode_step(cfg: LlamaConfig, paged: PagedKVConfig,
                     num_slots: int, top_k: Optional[int],
                     top_p: Optional[float], *, return_probs: bool = False):
    """One compiled program: one token for ALL ``num_slots`` slots. Each
    slot feeds back its last token at its own position, writes K/V into its
    own blocks (inactive slots write to trash), and samples with its own
    key/temperature. Admission, retirement and raggedness are pure data —
    the program never recompiles. The table WIDTH is read from the
    argument shape, not the pool config.

    ``return_probs=True`` is the DRAFT variant (serving/speculate.py):
    identical cache indexing, key discipline and sampling, but the program
    additionally returns the sampling distribution ``q`` per slot (post
    temperature/top_k/top_p — the ``q`` of the rejection test, so
    acceptance uses exactly the distribution the proposal was drawn from).
    One body serves both so a fix to the paged-cache math can never drift
    between target and draft."""
    bl = paged.block_len

    @partial(jax.jit, donate_argnums=(0,))
    def decode_step(pool: dict, params: dict, fused: dict,
                    tables: jnp.ndarray, last_tok: jnp.ndarray,
                    pos: jnp.ndarray, keys: jnp.ndarray,
                    temps: jnp.ndarray, active: jnp.ndarray):
        mb = tables.shape[1]
        blk_idx = jnp.minimum(pos // bl, mb - 1)
        own = jnp.take_along_axis(tables, blk_idx[:, None], axis=1)[:, 0]
        wblk = jnp.where(active, own, TRASH_BLOCK)
        woff = pos % bl
        h, pool, stats = _forward_paged(
            params, fused, last_tok[:, None], pool, tables, pos[:, None],
            wblk[:, None], woff[:, None], cfg, active[:, None])
        logits = _head(params, h, cfg)[:, 0, :]                    # [S, V]
        with jax.named_scope("sample"):
            split = jax.vmap(jax.random.split)(keys)               # [S, 2, 2]
            subs = split[:, 1]
            # Only ACTIVE slots consume randomness: a slot still
            # mid-prefill (or free) must keep its key untouched, or its
            # stream would start shifted relative to ``generate``'s by
            # however many decode steps happened to run before its
            # admission finished.
            new_keys = jnp.where(active[:, None], split[:, 0], keys)
            toks = jax.vmap(
                lambda k, l, t: _sample_slot(k, l[None], t, top_k, top_p)[0]
            )(subs, logits, temps)
        if stats is not None:       # a model with expert layers
            return pool, toks, new_keys, stats
        if not return_probs:
            return pool, toks, new_keys
        # Greedy slots' q is unused (their acceptance is the argmax
        # comparison); it is still computed, ``where``-select style, so
        # one compile serves any per-slot mix.
        safe_t = jnp.where(temps > 0, temps, 1.0)[:, None]
        q = jax.nn.softmax(
            generate.filter_logits(logits / safe_t, top_k, top_p), axis=-1)
        return pool, toks, q, new_keys

    return decode_step


# ----------------------------------------------------------------- the engine

class TokenEvent(NamedTuple):
    """One emitted token: ``first`` marks the TTFT token (sampled by the
    final prefill chunk), ``done`` that the slot retired with this token."""
    slot: int
    token: int
    first: bool
    done: bool


class _Slot:
    __slots__ = ("blocks", "prompt", "max_new", "produced", "prefill_off",
                 "phase", "seq", "shared", "prompt_key", "registered")

    def __init__(self, blocks, prompt, max_new, seq, *, shared=0,
                 prompt_key=None):
        self.blocks = blocks          # owned pool block indices (refs held
                                      # on the first ``shared`` of them)
        self.prompt = prompt          # np.int32 [Tp]
        self.max_new = max_new
        self.produced = 0
        self.prefill_off = 0          # tokens of prompt already prefilled
        self.phase = "prefill"        # "prefill" -> "decode"
        self.seq = seq                # admission order (prefill is FCFS by
                                      # THIS, not by slot index — a freed
                                      # low slot must not jump the line)
        self.shared = shared          # leading blocks mapped read-only from
                                      # an identical prompt prefix (CoW)
        self.prompt_key = prompt_key  # tuple(prompt) for prefix-cache keys
        self.registered = shared      # full prompt blocks published into
                                      # the prefix cache so far


class Engine:
    """Slots + compiled steps + block plumbing. Queueing, time and the
    request events live one layer up (scheduler.py); this class only knows
    how to admit a request into a free slot, advance prefill by one chunk,
    decode one token for everyone, and retire finished slots (freeing
    their blocks immediately).

    ``step()`` is one token boundary: at most one prefill chunk (FCFS over
    mid-prefill slots — the chunked-prefill interleave), then one decode
    step if any slot is decoding. Returns the ``TokenEvent``s produced.

    What a step costs the host is timed by cause in ``self.spans``
    (telemetry/trace.py ``Spans``: no event log, nothing of requests):
    ``engine.step`` round ``engine.prefill.{stage,dispatch,fetch}`` and
    ``engine.decode.{stage,dispatch,fetch,book}``. Under a live profiler
    the same spans stand on its timeline, the dispatch spans with the
    step's work as counters (``engine.prefill.dispatch``: ``slot``,
    ``seq``, ``off``, ``n_valid``, ``final``, ``attended_positions`` = the
    key positions the chunk's attention visits: the table's full width
    where it gathers, the live keys ``off + n_valid`` rounded up to the
    kernel's key block where the kernel is its attention
    (``chunk_attention_path``); ``engine.decode.dispatch``:
    ``dispatch`` = ``decode_dispatches`` as the step began, ``active``
    decoding slots, ``live_positions`` = the cache positions the step must
    attend to, sum of ``pos + 1`` over them, ``gathered_positions`` = what
    the program reads as built: ``num_slots`` x table width passed x
    ``block_len`` where it gathers, the decoding slots' live blocks whole,
    sum of ``ceil((pos + 1) / block_len)`` x ``block_len``, where the
    kernel is its attention (``paged_attention_path``); their ratio is the
    share of the read that was required; ``engine.decode.book``:
    ``emitted``). A model with
    expert layers adds ``pairs_routed`` to both dispatch spans and, read
    with the sampled tokens at the host's wait for the device,
    ``pairs_held``, ``experts_hit``, ``max_pairs`` to
    ``engine.decode.book``, with the same counts of the prefill chunks
    dispatched since the last wait as ``chunk_*`` there or on
    ``engine.prefill.fetch`` (``_note_routing``; totals in ``routing``).
    """

    def __init__(self, params: dict, cfg: LlamaConfig, paged: PagedKVConfig,
                 num_slots: int, *, prefill_chunk: int = 16,
                 top_k: Optional[int] = None, top_p: Optional[float] = None,
                 engine_id: Optional[int] = None,
                 speculate: Optional["SpecConfig"] = None,
                 prefix_share: bool = False):
        if num_slots < 1 or prefill_chunk < 1:
            raise ValueError(f"num_slots={num_slots}, "
                             f"prefill_chunk={prefill_chunk}")
        self.cfg = cfg
        # What the programs dispatch on: the kind of each layer and of the
        # attention, the cache's row, the experts held here.
        self.desc = describe(cfg)
        if not self.desc.plain and (speculate is not None or prefix_share):
            if self.desc.mixer is not None:
                raise NotImplementedError(
                    "speculation and prefix sharing serve LlamaConfig models "
                    "only: a slot's recurrent state (kvcache.init_state) is "
                    "one value for the whole prefix, so it cannot be rolled "
                    "back past a rejected draft nor shared by the block "
                    "(ROADMAP.md)")
            raise NotImplementedError(
                "speculation and prefix sharing serve LlamaConfig models "
                "only: a draft for, and shared blocks of latent rows under, "
                f"a model of layers {set(self.desc.layer_kinds)} with "
                "latent attention are not built (ROADMAP.md)")
        mx = self.desc.mixer
        if mx is not None and prefill_chunk > mx.chunk \
                and prefill_chunk % mx.chunk:
            raise ValueError(f"prefill_chunk={prefill_chunk}: the chunked "
                             f"scan takes whole chunks of {mx.chunk}, or "
                             "one shorter chunk")
        self.paged = paged
        self.num_slots = num_slots
        self.prefill_chunk_len = prefill_chunk
        # Fleet seam (serving/fleet.py): which replica this engine is.
        # Purely a label — it tags the compile-watch names below (so an
        # N-engine run's 2N compile events attribute per engine) and rides
        # through the scheduler into request_*/route/deploy telemetry.
        self.engine_id = engine_id
        # ONE copy of each weight, in the layout the programs read: the
        # embedding, final norm and head as given, the layers fused
        # (``generate._fuse_blocks``: q/k/v and gate/up concatenated, once)
        # or, for a described model, its stacked runs as they are. The
        # caller's tree is not kept; ``boot`` remembers its shapes and
        # placement for the hot-swap contract, and ``params`` rebuilds it.
        self.boot = jax.tree.map(LeafSpec, params)
        self._set_weights(params)
        # Two kinds of cache in one donated tree: the paged pool, and for a
        # model with a state-space mixer the state store (``kvcache.
        # init_state``: "s" and "tail", a row a slot; else nothing).
        self.pool = {**init_pool(cfg, paged), **init_state(cfg, num_slots)}
        self.state_bytes_per_slot = state_bytes_per_slot(cfg)
        # Whether the decode program's attention is the kernel, which reads
        # the decoding slots' live blocks and no padded position: what
        # ``gathered_positions`` counts (``_dispatch_counters``).
        self._decode_reads_live_blocks = "k" in self.pool and (
            paged_attention_path(
                1, *self.pool["k"].shape[3:], self.pool["k"].dtype,
                *(() if mx is None else (paged.block_len,))
            )["impl"] == "pallas")
        # The key block of the prefill program's attention where that is the
        # kernel, which visits a chunk's live keys in whole blocks and no
        # padded one, else 0: what ``attended_positions`` counts.
        self._chunk_key_block = 0
        if self.desc.attention is not None and chunk_attention_path(
                prefill_chunk, paged.max_seq_len,
                self.desc)["impl"] == "pallas":
            from ..ops import chunk_attention as ca
            self._chunk_key_block = ca.blocks(prefill_chunk,
                                              paged.max_seq_len)[1]
        self.allocator = BlockAllocator(paged.num_blocks)
        self._admit_seq = 0
        self.slots: List[Optional[_Slot]] = [None] * num_slots
        # Host-side slot state, shipped to the device each step as COPIES
        # (jnp.array, never jnp.asarray: a zero-copy handoff would freeze
        # these buffers read-only under the host's feet on the CPU
        # backend). Tiny [S] rows; only the pool is device-resident and
        # donated. Keys live device-side: decode returns the split batch.
        self.tables = np.full((num_slots, paged.max_blocks_per_seq),
                              TRASH_BLOCK, np.int32)
        self.pos = np.zeros(num_slots, np.int32)
        self.last_tok = np.zeros(num_slots, np.int32)
        self.temps = np.zeros(num_slots, np.float32)
        self.keys = jnp.zeros((num_slots, 2), jnp.uint32)
        # Copy-on-write prefix sharing (kvcache.py): a host-side map from
        # a prompt's leading n·block_len tokens to the physical block
        # holding tokens [(n-1)·bl, n·bl). Entries are published only once
        # the owning slot's prefill has WRITTEN the block, and evicted
        # when the last reference frees it — sharing is among live
        # requests (the persistent-LRU extension is the documented next
        # step). ``_block_key`` is the eviction reverse map.
        self.prefix_share = prefix_share
        self._prefix_blocks: Dict[tuple, int] = {}
        self._block_key: Dict[int, tuple] = {}
        # Compile/retrace observability (telemetry/introspect.py): the
        # engine's contract is a DOCUMENTED program set — two programs
        # (prefill_chunk + decode_step) without speculation, three
        # (+ verify_step; decode_step idles) plus the draft's two with it
        # — admission, retirement and raggedness are data, never shapes.
        # The watches enforce a budget of one compile each (growth past it
        # is a flagged retrace) and emit ``compile`` events once the
        # scheduler binds its event stream (introspect.bind_events).
        from ..telemetry import introspect
        tag = "" if engine_id is None else f"[{engine_id}]"
        self._prefill = introspect.watch(
            make_prefill_chunk(cfg, paged, prefill_chunk, top_k, top_p),
            name=f"serving/prefill_chunk{tag}", max_caches=1)
        self._decode = introspect.watch(
            make_decode_step(cfg, paged, num_slots, top_k, top_p),
            name=f"serving/decode_step{tag}", max_caches=1)
        # Speculative decoding (serving/speculate.py): the draft engine
        # (own pool over the SAME block tables, own two programs) and the
        # one-dispatch k+1-position verify program.
        self.spec = speculate
        self.last_spec: Optional[dict] = None
        self.spans = Spans()           # host seconds of a step, by cause
        # Expert layers: each program hands back, per expert layer, the
        # pairs computed here, the held experts hit and the most one took
        # (``models/experts.py::STATS``). They are read with the sampled
        # tokens, at the host's next wait for the device and never at one
        # of their own; a prefill chunk's wait there until then.
        ex = self.desc.experts
        self._pairs_a_token = (0 if ex is None else ex.top_k
                               * self.desc.layer_kinds.count("experts"))
        self._chunk_stats: list = []
        self.routing = {"pairs_routed": 0, "pairs_held": 0,
                        "experts_hit": 0, "max_pairs": 0}
        self.decode_dispatches = 0     # verify or plain decode calls
        self.decode_tokens = 0         # tokens those dispatches emitted
        self.draft_dispatches = 0
        if speculate is not None:
            from .speculate import DraftEngine, make_verify_step
            self.draft = DraftEngine(
                speculate, cfg, paged, num_slots,
                prefill_chunk=prefill_chunk, top_k=top_k, top_p=top_p,
                engine_id=engine_id)
            self._verify = introspect.watch(
                make_verify_step(cfg, paged, num_slots, speculate.k,
                                 top_k, top_p),
                name=f"serving/verify_step{tag}", max_caches=1)
        else:
            self.draft = None
            self._verify = None

    def watches(self) -> list:
        """The engine's CompileWatch set — its documented program budget.
        Two entries without speculation (byte-for-byte the historical
        contract), five with it (prefill + decode + verify + the draft's
        prefill + decode)."""
        ws = [self._prefill, self._decode]
        if self.spec is not None:
            ws += [self._verify, self.draft._prefill, self.draft._decode]
        return ws

    # --------------------------------------------------------------- weights
    def _set_weights(self, params: dict, fused=None) -> None:
        layers = "blocks" if self.desc.plain else "runs"
        self._head = {k: v for k, v in params.items() if k != layers}
        if not self.desc.plain:
            self.fused = tuple(params["runs"])
        else:
            self.fused = (fused if fused is not None
                          else generate._fuse_blocks(params["blocks"]))

    @property
    def weights(self) -> tuple:
        """What the engine holds on the device, one copy of each weight."""
        return self._head, self.fused

    @property
    def params(self) -> dict:
        """The tree the engine was given (or last swapped to), rebuilt from
        what it holds: a described model's as it is, a ``LlamaConfig``
        model's with the fused q/k/v and gate/up split again (copies, made
        on each call: for tests and tools, not for a hot path)."""
        if not self.desc.plain:
            return {**self._head, "runs": self.fused}
        f = self.fused
        wq, wk, wv = jnp.split(f["w_qkv"], 3, axis=-1)
        w_gate, w_up = jnp.split(f["w_gu"], 2, axis=-1)
        return {**self._head, "blocks": {
            "attn_norm": f["attn_norm"], "wq": wq, "wk": wk, "wv": wv,
            "wo": f["wo"], "mlp_norm": f["mlp_norm"], "w_gate": w_gate,
            "w_up": w_up, "w_down": f["w_down"]}}

    def _note_routing(self, span, stats=None, tokens: int = 0) -> None:
        """At a wait for the device: write this step's routing counters,
        and those of the prefill chunks dispatched since the last wait
        (``chunk_*``), on ``span`` and add them to ``self.routing``."""
        if not self._pairs_a_token:
            return
        found = {}
        for prefix, batch in (("", [] if stats is None
                               else [(stats, tokens)]),
                              ("chunk_", self._chunk_stats)):
            if not batch:
                continue
            st = np.stack([np.asarray(s) for s, _ in batch])   # [n, L, 3]
            got = {"pairs_routed": self._pairs_a_token
                   * sum(n for _, n in batch),
                   "pairs_held": int(st[..., 0].sum()),
                   "experts_hit": int(st[..., 1].sum()),
                   "max_pairs": int(st[..., 2].max())}
            for k, v in got.items():
                found[prefix + k] = v
                self.routing[k] = (max(self.routing[k], v)
                                   if k == "max_pairs"
                                   else self.routing[k] + v)
            if prefix:
                found["chunks"] = len(batch)
        self._chunk_stats = []
        span.set_metadata(**found)

    # ------------------------------------------------------------- admission
    def required_blocks(self, prompt_len: int, max_new: int) -> int:
        """Positions written are ``0..prompt_len+max_new-2`` (the final
        sampled token is never fed back — ``generate``'s horizon)."""
        return blocks_for(prompt_len + max_new - 1, self.paged.block_len)

    def _shared_prefix(self, prompt) -> List[int]:
        """Physical blocks an admission of ``prompt`` can map read-only:
        the longest chain of FULL prompt blocks whose exact token prefix
        is already published in the prefix cache (i.e. written by a live
        request). Registration is prefix-ordered, so the walk stops at
        the first miss."""
        if not self.prefix_share:
            return []
        bl = self.paged.block_len
        key = tuple(int(t) for t in prompt)
        shared: List[int] = []
        for n in range(1, len(key) // bl + 1):
            b = self._prefix_blocks.get(key[:n * bl])
            if b is None:
                break
            shared.append(b)
        return shared

    def free_slot(self) -> Optional[int]:
        for s, slot in enumerate(self.slots):
            if slot is None:
                return s
        return None

    def can_admit(self, prompt_len: int, max_new: int,
                  prompt=None) -> bool:
        """``prompt`` (the token ids) lets CoW-sharing engines credit the
        blocks a shared prefix saves; without it the check is the
        conservative full-reservation one (always safe — sharing only
        ever reduces the fresh-block need)."""
        if self.free_slot() is None:
            return False
        need = self.required_blocks(prompt_len, max_new)
        if prompt is not None:
            need -= len(self._shared_prefix(prompt))
        return need <= self.allocator.free_blocks

    def admit(self, prompt, max_new: int, *, temperature: float = 0.0,
              key: Optional[jax.Array] = None) -> int:
        """Place a request into a free slot and reserve its WORST-CASE
        blocks up front. All-or-nothing reservation is the liveness
        guarantee: an admitted request can always run to completion, so
        pool exhaustion can only ever queue admissions, never deadlock
        in-flight work (scheduler.py holds the policy argument).

        With ``prefix_share``, full prompt blocks already written by a
        live request with the identical prefix are mapped READ-ONLY into
        this slot's table (allocator refcount, not a fresh grant) and the
        reservation shrinks by that many blocks; the slot's own writes
        start at the first un-shared position (its prefill passes
        ``write_from``), so a shared block is never written twice — the
        divergent tail always lands in this slot's private blocks."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        tp, mx = len(prompt), int(max_new)
        if tp < 1 or mx < 1:
            raise ValueError(f"empty request: prompt_len={tp}, max_new={mx}")
        if tp + mx - 1 > self.paged.max_seq_len:
            raise ValueError(
                f"request needs {tp + mx - 1} cache positions but the pool "
                f"serves at most max_blocks_per_seq * block_len = "
                f"{self.paged.max_seq_len}")
        s = self.free_slot()
        if s is None:
            raise RuntimeError("no free slot")
        shared = self._shared_prefix(prompt)
        fresh = self.allocator.alloc(self.required_blocks(tp, mx)
                                     - len(shared))
        if fresh is None:
            raise RuntimeError("pool exhausted")
        if shared:
            self.allocator.share(shared)
        blocks = shared + fresh
        self._admit_seq += 1
        self.slots[s] = _Slot(blocks, prompt, mx, self._admit_seq,
                              shared=len(shared),
                              prompt_key=(tuple(int(t) for t in prompt)
                                          if self.prefix_share else None))
        # Skip prefilling the shared region (its K/V is already in the
        # pool, bitwise what this slot would write) — but always run the
        # chunk holding the LAST prompt token: the first-token sample
        # needs its hidden state, which only K/V survives of the shared
        # computation. Writes below write_from go to trash.
        self.slots[s].prefill_off = min(len(shared) * self.paged.block_len,
                                        tp - 1)
        self.tables[s] = TRASH_BLOCK
        self.tables[s, :len(blocks)] = blocks
        self.pos[s] = 0
        self.temps[s] = float(temperature)
        if key is None:
            if temperature > 0:
                raise ValueError("sampling (temperature>0) requires a key")
            key = jax.random.PRNGKey(0)      # unused by greedy (generate's
        self.keys = self.keys.at[s].set(key)  # own placeholder convention)
        if self.draft is not None:
            self.draft.admit_key(s, temperature, key)
        return s

    # ----------------------------------------------------------- one boundary
    @property
    def busy(self) -> bool:
        return any(slot is not None for slot in self.slots)

    def blocks_in_use(self) -> int:
        return self.allocator.in_use

    def state_bytes_in_use(self) -> int:
        """Bytes of the state store that admitted requests own: a slot's
        rows from admission to retirement (0 without a state-space mixer)."""
        return self.state_bytes_per_slot * sum(
            sl is not None for sl in self.slots)

    # ------------------------------------------------------- weight hot-swap
    def swap_params(self, params: dict, *, fused: Optional[dict] = None
                    ) -> None:
        """Swap to new weights at the CURRENT token boundary — the live
        train→deploy seam (serving/deploy.py). Legal between ``step()``
        calls only (the host drives the engine, so outside a ``step()``
        nothing is in flight by construction); in-flight streams are NOT
        dropped — their next token is sampled under the new weights over
        the KV each slot already wrote, and nothing already emitted
        changes (the hot-swap determinism bar in
        tests/test_fleet_serving.py: a same-weights swap is bitwise
        invisible; a new-weights swap changes only tokens sampled after
        the boundary).

        The new tree must match the old one leaf-for-leaf in shape and
        dtype: params are DATA to the two compiled programs, so an equal
        tree swaps with zero recompiles (the engine's two-programs
        contract survives any number of publishes), while a different
        shape would silently retrace — rejected loudly instead. Placement
        is normalized to the boot params' (``_match_placement``) for the
        same reason: a checkpoint-restored tree arrives committed, and
        committed-ness is part of the jit cache key.

        ``fused`` (the ``generate._fuse_blocks`` view of ``params``) can
        be passed precomputed so an N-engine fleet fuses once per publish,
        not once per engine."""
        check_swappable(self.boot, params)
        self._set_weights(_match_placement(params, self.boot),
                          None if fused is None
                          else _match_placement(fused, self.fused))

    def step(self) -> List[TokenEvent]:
        """One token boundary: one prefill chunk (if a slot is mid-prefill),
        then one decode step — or, with speculation, one draft-propose +
        verify round — over the decoding slots."""
        events: List[TokenEvent] = []
        self.last_spec = None
        with self.spans("engine.step"):
            prefilling = [(sl.seq, i) for i, sl in enumerate(self.slots)
                          if sl is not None and sl.phase == "prefill"]
            if prefilling:
                events.extend(self._advance_prefill(min(prefilling)[1]))
            if any(sl is not None and sl.phase == "decode"
                   for sl in self.slots):
                events.extend(self._advance_spec_decode()
                              if self.spec is not None
                              else self._advance_decode())
        return events

    def _register_prefix_blocks(self, s: int) -> None:
        """Publish the full prompt blocks slot ``s``'s prefill has now
        written (or shares) into the prefix cache, so later admissions
        with the identical prefix can map them. First writer wins; an
        entry for the same prefix already present (the donor, or a
        concurrent identical prompt that couldn't share yet) is kept."""
        slot = self.slots[s]
        bl = self.paged.block_len
        while ((slot.registered + 1) * bl <= slot.prefill_off
               and (slot.registered + 1) * bl <= len(slot.prompt)):
            n = slot.registered + 1
            key = slot.prompt_key[:n * bl]
            block = int(self.tables[s, n - 1])
            if key not in self._prefix_blocks:
                self._prefix_blocks[key] = block
                self._block_key[block] = key
            slot.registered = n

    def _advance_prefill(self, s: int) -> List[TokenEvent]:
        slot = self.slots[s]
        with self.spans("engine.prefill.stage"):
            tc = self.prefill_chunk_len
            off = slot.prefill_off
            n_valid = min(tc, len(slot.prompt) - off)
            chunk = np.zeros(tc, np.int32)
            chunk[:n_valid] = slot.prompt[off:off + n_valid]
            is_final = off + n_valid >= len(slot.prompt)
            write_from = slot.shared * self.paged.block_len
            table_row = jnp.array(self.tables[s])
            chunk_j = jnp.array(chunk)
            scalars = (jnp.int32(off), jnp.int32(n_valid),
                       jnp.int32(write_from))
            temp = jnp.float32(self.temps[s])
        routed = ({"pairs_routed": n_valid * self._pairs_a_token}
                  if self._pairs_a_token else {})
        attended, bk = self.paged.max_seq_len, self._chunk_key_block
        if bk:
            attended = min(blocks_for(off + n_valid, bk) * bk, attended)
        mx, of_slot = self.desc.mixer, ()
        if mx is not None:
            # the slot whose state the chunk carries on, and the chunks of
            # the scan that hold a real position
            of_slot = (jnp.int32(s),)
            routed = {"state_slots": 1, "scan_chunks": blocks_for(
                n_valid, min(mx.chunk, tc))}
        with self.spans("engine.prefill.dispatch", slot=s, seq=slot.seq,
                        off=off, n_valid=n_valid, final=int(is_final),
                        attended_positions=attended, **routed):
            self.pool, tok, new_key, *stats = self._prefill(
                self.pool, self._head, self.fused,
                table_row, chunk_j, *scalars, self.keys[s], temp, *of_slot)
            if stats:
                self._chunk_stats.append((stats[0], n_valid))
            if self.draft is not None:
                # Mirror the chunk into the draft pool (same table row,
                # same positions, the draft's weights) so proposals can
                # attend over the full prompt. Shared blocks are shared
                # there too — the donor's draft prefill wrote them — so the
                # same write_from masking applies.
                self.draft.prefill_chunk(table_row, chunk_j, *scalars,
                                         self.temps[s])
                # The mirror is a real draft dispatch: without it the
                # JSON's draft-cost line under-reports by one dispatch per
                # prefill chunk (~15% on the CI smoke's workload) and a
                # real small draft sized from it would look cheaper than
                # it is.
                self.draft_dispatches += 1
            if is_final:
                self.keys = self.keys.at[s].set(new_key)
        slot.prefill_off = off + n_valid
        if self.prefix_share:
            self._register_prefix_blocks(s)
        if not is_final:
            # Intermediate chunk: K/V written; the sampled token and split
            # key are discarded so the slot's RNG stream stays exactly
            # generate's (one split for the whole prefill).
            return []
        with self.spans("engine.prefill.fetch") as fetch:
            first = int(tok)            # the host waits for the device
            self._note_routing(fetch)
        slot.phase = "decode"
        slot.produced = 1
        self.pos[s] = len(slot.prompt)
        self.last_tok[s] = first
        done = slot.produced >= slot.max_new
        if done:
            self._retire(s)
        return [TokenEvent(s, first, first=True, done=done)]

    def _dispatch_counters(self, active: np.ndarray, tables,
                           tq: int) -> dict:
        """The counters of ``engine.decode.dispatch`` (class docstring), for
        a dispatch of ``tq`` query rows a slot."""
        routed = ({"pairs_routed": int(active.sum()) * self._pairs_a_token}
                  if self._pairs_a_token else {})
        if self.desc.mixer is not None:
            # the slots whose state the step reads and writes
            routed = {"state_slots": int(active.sum())}
        bl, width = self.paged.block_len, int(tables.shape[1])
        live = self.pos[active].astype(np.int64) + 1
        if tq == 1 and self._decode_reads_live_blocks:
            gathered = int((np.minimum(-(-live // bl), width) * bl).sum())
        else:
            gathered = self.num_slots * width * bl
        return {**routed, "dispatch": self.decode_dispatches,
                "active": int(active.sum()),
                "live_positions": int(live.sum()),
                "gathered_positions": gathered}

    def _advance_decode(self) -> List[TokenEvent]:
        with self.spans("engine.decode.stage"):
            active = np.array([sl is not None and sl.phase == "decode"
                               for sl in self.slots])
            args = (jnp.array(self.tables), jnp.array(self.last_tok),
                    jnp.array(self.pos), self.keys,
                    jnp.array(self.temps), jnp.array(active))
        with self.spans("engine.decode.dispatch",
                        **self._dispatch_counters(active, self.tables, 1)):
            self.pool, toks, new_keys, *stats = self._decode(
                self.pool, self._head, self.fused, *args)
        with self.spans("engine.decode.fetch"):
            toks = np.asarray(toks)     # the host waits for the device
        self.keys = new_keys
        events = []
        with self.spans("engine.decode.book") as book:
            for s in np.nonzero(active)[0]:
                slot = self.slots[s]
                tok = int(toks[s])
                slot.produced += 1
                self.pos[s] += 1
                self.last_tok[s] = tok
                done = slot.produced >= slot.max_new
                if done:
                    self._retire(s)
                events.append(TokenEvent(int(s), tok, first=False,
                                         done=done))
            self.decode_dispatches += 1
            self.decode_tokens += len(events)
            book.set_metadata(emitted=len(events))
            self._note_routing(book, stats[0] if stats else None,
                               int(active.sum()))
        return events

    def _advance_spec_decode(self) -> List[TokenEvent]:
        """One speculative round (serving/speculate.py): k draft decode
        dispatches propose, one cache-fill dispatch keeps the draft pool
        whole, ONE target verify dispatch scores all k+1 window positions
        and accepts a prefix. Emits ``min(accepted + 1, remaining)``
        tokens per active slot — the greedy ones bitwise ``generate()``'s
        — and records the round's proposal accounting in ``last_spec``
        (the scheduler's ``speculate`` event, schema v7)."""
        k = self.spec.k
        with self.spans("engine.decode.stage"):
            active_l = [sl is not None and sl.phase == "decode"
                        for sl in self.slots]
            active = np.array(active_l)
            remaining = np.array([sl.max_new - sl.produced if a else 0
                                  for a, sl in zip(active_l, self.slots)],
                                 np.int32)
            live = np.minimum(k + 1,
                              np.maximum(remaining, 1)).astype(np.int32)
            tables = jnp.array(self.tables)
            pos = jnp.array(self.pos)
            temps = jnp.array(self.temps)
            active_j = jnp.array(active)
            live_j = jnp.array(live)
        with self.spans("engine.decode.dispatch",
                        **self._dispatch_counters(active, self.tables,
                                                  k + 1)):
            drafts, draft_probs = self.draft.propose(
                tables, jnp.array(self.last_tok), pos, temps, active_j,
                live_j)
            self.draft_dispatches += k + 1
            window = jnp.concatenate([jnp.array(self.last_tok)[:, None],
                                      drafts], axis=1)
            self.pool, out, accepted, new_keys = self._verify(
                self.pool, self._head, self.fused, tables, window,
                draft_probs, pos, live_j, self.keys, temps, active_j)
        with self.spans("engine.decode.fetch"):
            out = np.asarray(out)
            accepted = np.asarray(accepted)
        self.keys = new_keys
        self.decode_dispatches += 1
        events: List[TokenEvent] = []
        n_active = int(active.sum())
        used = proposed = 0
        with self.spans("engine.decode.book") as book:
            for s in np.nonzero(active)[0]:
                slot = self.slots[s]
                emit = min(int(accepted[s]) + 1, int(remaining[s]))
                # The draft really proposed min(k, remaining) tokens for
                # this slot — the propose loop masks rows past the live
                # window, so horizon truncation is not a draft failure and
                # must not read as rejection in the acceptance rate.
                proposed += min(k, int(remaining[s]))
                used += min(int(accepted[s]), emit)
                for i in range(emit):
                    tok = int(out[s, i])
                    slot.produced += 1
                    self.pos[s] += 1
                    self.last_tok[s] = tok
                    done = slot.produced >= slot.max_new
                    if done:
                        self._retire(s)
                    events.append(TokenEvent(int(s), tok, first=False,
                                             done=done))
            self.decode_tokens += len(events)
            book.set_metadata(emitted=len(events))
        self.last_spec = {"k": k, "slots": n_active,
                          "proposed": proposed, "accepted": used,
                          "rejected": proposed - used,
                          "emitted": len(events)}
        return events

    def retire(self, s: int) -> None:
        """Retire slot ``s`` early, before its ``max_new`` horizon — the
        scheduler's EOS path. The slot's WHOLE reservation (written blocks
        and the never-to-be-written worst-case tail alike) returns to the
        pool at this token boundary. Safe at any phase: the freed blocks'
        stale K/V is unreachable once the table row resets to trash, and
        a future owner overwrites before it reads (position masking)."""
        if self.slots[s] is None:
            raise ValueError(f"retire({s}): slot is not active")
        self._retire(s)

    def _retire(self, s: int) -> None:
        """Free the slot and its blocks IMMEDIATELY (the continuous-batching
        point: the next token boundary can re-use them). Under CoW the
        free is a refcount decrement for shared blocks; blocks that
        actually return to the pool lose their prefix-cache entries (a
        later admission must never map a block the allocator may have
        re-granted)."""
        freed = self.allocator.free(self.slots[s].blocks)
        for b in freed:
            key = self._block_key.pop(b, None)
            if key is not None:
                self._prefix_blocks.pop(key, None)
        self.slots[s] = None
        self.tables[s] = TRASH_BLOCK
        self.pos[s] = 0
        self.temps[s] = 0.0
