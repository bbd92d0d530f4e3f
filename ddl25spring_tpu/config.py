"""Workload configuration dataclasses.

The reference has no flag system: hyperparameters live as module constants and
homework-text defaults (reference: lab/tutorial_1b/primer/intro.py:7-23 for the
tiny-Llama constants; lab/homework-1.ipynb cell 5 for the FL defaults N=100,
lr=0.01, C=0.1, E=1, B=100, rounds=10, iid=True, seed=10). Here each workload
gets one frozen dataclass whose *defaults are the reference's parity configs*,
so `FLConfig()` with no arguments reproduces the homework setting.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Sequence, Tuple


@dataclass(frozen=True)
class FLConfig:
    """Horizontal federated learning (FedSGD / FedAvg) configuration.

    Defaults mirror the homework-1 defaults (reference: lab/homework-1.ipynb
    cell 5 and lab/tutorial_1a/hfl_complete.py:256-386).
    """

    nr_clients: int = 100          # N
    client_fraction: float = 0.1   # C — fraction of clients sampled per round
    batch_size: int = 100          # B — -1 means full local dataset (∞)
    epochs: int = 1                # E — local epochs per round (FedAvg)
    lr: float = 0.01               # η
    rounds: int = 10
    iid: bool = True
    seed: int = 10

    @property
    def clients_per_round(self) -> int:
        # max(1, C·N) like the reference's client sampling.
        return max(1, int(self.client_fraction * self.nr_clients))


@dataclass(frozen=True)
class LlamaConfig:
    """tiny-Llama model configuration.

    Defaults are the canonical config used by every reference LLM experiment
    (reference: lab/tutorial_1b/primer/intro.py:7-10 — dmodel=288, 6 heads,
    6 layers, seq 256; Adam lr 8e-4 at intro.py:22).
    """

    vocab_size: int = 32000
    dmodel: int = 288
    num_heads: int = 6
    n_layers: int = 6
    ctx_size: int = 256
    ffn_hidden: Optional[int] = None   # None -> 4 * dmodel (SwiGLU-gated)
    padding_idx: Optional[int] = None
    norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    dtype: str = "float32"             # computation dtype ("bfloat16" on TPU)
    param_dtype: str = "float32"
    # Attention inner: "xla" (batched dot_generals over a materialized
    # score tensor), "pallas" (the flash kernel, ops/flash_attention.py), or
    # "auto": the flash kernel iff the backend is a TPU and the sequence is
    # at least ``flash_min_seq`` (models/llama.py::attention_path decides
    # where the step is traced). The training cell runs "auto" at T=4096,
    # so the kernel; the ledger reads it at 25% of its compute roofline at
    # head size 128 (PERF.md section 5, ``flash_attn_roofline.train``).
    # Where the two inners cross is not measured at published widths
    # (ROADMAP S5).
    attention_impl: str = "auto"
    flash_min_seq: int = 256
    # Stream flash-kernel operands in the dense [BH, Dh, T] layout instead of
    # [BH, T, Dh]. At head sizes below 128 lanes the row-major layout pads
    # every q/k/v/o and gradient transfer to 128 lanes (2.67x the bytes at
    # Dh=48); dh-major is dense at any head size. Same math and MXU shapes
    # (ops/flash_attention.py). The training cell runs it at Dh=128, where
    # row-major pads nothing: the two are not compared at published widths
    # (ROADMAP S5).
    flash_dh_major: bool = True
    # Pallas block size cap (block_q = block_k = min(T, flash_block)). At
    # T <= flash_block one block holds the whole sequence (one grid step per
    # (b, h), no online-softmax recurrence); the training cell runs T=4096
    # in blocks of 512. The kernel's own default of 128 keeps VMEM smaller.
    # Which block size is fastest is not measured at published widths
    # (ROADMAP S5).
    flash_block: int = 512
    # Dtype of the materialized [B·H, T, T] attention score tensor. The
    # default fp32 is what the PP/SP equivalence tests are calibrated to;
    # "bfloat16" halves that tensor's bytes (softmax max/denominator stay
    # fp32) at ~1e-2 logit drift; no cell sets it, and what it buys is not
    # measured at published widths (ROADMAP S5).
    # Applies to the XLA attention path only: the pallas flash kernel never
    # materializes the score tensor in the first place (fp32 accumulators,
    # tile-local scores), and SP's ring attention owns its own fp32
    # online-softmax accumulation — on those paths this knob is a no-op.
    softmax_dtype: str = "float32"
    # Rematerialize block activations in backward (jax.checkpoint) — trades
    # FLOPs for HBM, the TPU-native answer to activation memory pressure.
    remat: bool = False

    @property
    def head_dim(self) -> int:
        assert self.dmodel % self.num_heads == 0
        return self.dmodel // self.num_heads

    @property
    def ffn_dim(self) -> int:
        return self.ffn_hidden if self.ffn_hidden is not None else 4 * self.dmodel

    def replace(self, **kw) -> "LlamaConfig":
        return dataclasses.replace(self, **kw)


@dataclass(frozen=True)
class LatentAttention:
    """Latent attention (MLA): queries through a rank-``q_rank`` bottleneck,
    keys and values through ONE latent row a position, ``kv_rank`` values
    (after their norm) beside ``rope_dim`` rotated values that every head
    shares. The cache holds that row and nothing per head. RoPE is YaRN
    (``rope_factor`` 1: plain)."""

    q_rank: int
    kv_rank: int
    nope_dim: int                  # a head's un-rotated query/key part
    rope_dim: int                  # the rotated part, one key row for all heads
    v_dim: int                     # a head's value size
    rope_factor: float = 1.0
    rope_original_ctx: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    @property
    def row_dim(self) -> int:
        """Values a cache position holds in one layer."""
        return self.kv_rank + self.rope_dim

    @property
    def qk_dim(self) -> int:
        return self.nope_dim + self.rope_dim


@dataclass(frozen=True)
class ExpertLayer:
    """A layer of routed experts beside ``n_shared`` shared ones, as ONE chip
    of an expert-parallel deployment sees it: the router scores all
    ``n_experts`` (its published width), and this chip holds the contiguous
    range ``[held_start, held_start + held_count)`` of them."""

    n_experts: int
    top_k: int
    width: int                     # hidden width of each expert
    n_shared: int
    scale: float                   # routed_scaling_factor
    norm_topk: bool
    held_start: int
    held_count: int

    def __post_init__(self):
        if not (0 <= self.held_start
                and self.held_start + self.held_count <= self.n_experts
                and self.held_count >= 1 and self.top_k <= self.n_experts):
            raise ValueError(f"bad expert layer: {self}")


@dataclass(frozen=True)
class StateSpaceMixer:
    """A Mamba-2 mixer as ``models/state_space.py`` computes it: ``heads``
    heads of ``head_dim`` (``d_inner`` together, set by the config and not by
    an expansion factor), ``groups`` groups of B and C of ``state`` values, a
    causal depthwise convolution of ``conv`` taps over ``[x | B | C]``, a
    chunked scan in chunks of ``chunk`` for a prefill chunk, and a gated
    RMSNorm over each group's ``d_inner / groups`` values after the gate.
    What a slot carries is a state ``[heads, head_dim, state]`` in
    ``state_dtype`` (the recurrence's arithmetic is in that type too) and the
    convolution's last ``conv - 1`` inputs ``[conv - 1, conv_dim]``."""

    d_inner: int
    heads: int
    head_dim: int
    groups: int
    state: int
    conv: int
    chunk: int
    state_dtype: str = "float32"

    def __post_init__(self):
        if (self.heads * self.head_dim != self.d_inner
                or self.heads % self.groups or self.d_inner % self.groups):
            raise ValueError(f"bad state-space mixer: {self}")

    @property
    def conv_dim(self) -> int:
        """Channels of the convolution: x beside every group's B and C."""
        return self.d_inner + 2 * self.groups * self.state

    @property
    def proj_dim(self) -> int:
        """Width of the input projection: ``[z | x | B | C | dt]``."""
        return self.d_inner + self.conv_dim + self.heads


@dataclass(frozen=True)
class Multipliers:
    """The muP multipliers of a ``falcon_h1`` config, by its keys; ``ssm``
    scales the parts of the mixer's projection (z, x, B, C, dt in that
    order), ``mlp`` the gate before its silu and the down projection's
    result. All 1: no multiplier."""

    embedding: float = 1.0
    lm_head: float = 1.0
    attention_in: float = 1.0
    attention_out: float = 1.0
    key: float = 1.0
    ssm_in: float = 1.0
    ssm_out: float = 1.0
    ssm: Tuple[float, float, float, float, float] = (1.0,) * 5
    mlp: Tuple[float, float] = (1.0, 1.0)


@dataclass(frozen=True)
class ModelDescription:
    """A decoder as the serving engine reads it: one kind per layer
    (``"dense"``: SwiGLU of width ``ffn_hidden``; ``"experts"``:
    ``experts``; ``"parallel"``: a state-space ``mixer`` and attention side
    by side on one normed input, their outputs summed into one residual,
    then the SwiGLU), one attention kind for all layers (``attention``
    None: K and V per head, ``kv_heads`` of them (None: as many as query
    heads) of ``head_size`` (None: ``dmodel / num_heads``); else latent),
    and the sizes that follow. ``LlamaConfig`` models are described by
    ``describe``; a published ``config.json`` by ``from_published``."""

    vocab_size: int
    dmodel: int
    num_heads: int
    layer_kinds: Tuple[str, ...]
    ffn_hidden: int
    norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    ctx_size: int = 256
    dtype: str = "float32"
    param_dtype: str = "float32"
    attention: Optional[LatentAttention] = None
    experts: Optional[ExpertLayer] = None
    init_std: float = 0.02         # ``initializer_range``
    kv_heads: Optional[int] = None
    head_size: Optional[int] = None
    mixer: Optional[StateSpaceMixer] = None
    multipliers: Optional[Multipliers] = None

    def __post_init__(self):
        bad = set(self.layer_kinds) - {"dense", "experts", "parallel"}
        if bad or not self.layer_kinds:
            raise ValueError(f"layer kinds {sorted(bad)}: the engine has "
                             "'dense', 'experts' and 'parallel'")
        if "experts" in self.layer_kinds and self.experts is None:
            raise ValueError("expert layers without an ExpertLayer")
        if ("parallel" in self.layer_kinds) != (self.mixer is not None) \
                or (self.mixer is not None
                    and (set(self.layer_kinds) != {"parallel"}
                         or self.attention is not None)):
            raise ValueError("a state-space mixer stands beside K and V per "
                             "head in every layer, all of kind 'parallel'")
        if self.num_heads % self.num_kv_heads:
            raise ValueError(f"{self.num_heads} query heads over "
                             f"{self.num_kv_heads} key/value heads")

    @property
    def n_layers(self) -> int:
        return len(self.layer_kinds)

    @property
    def num_kv_heads(self) -> int:
        return self.num_heads if self.kv_heads is None else self.kv_heads

    @property
    def head_dim(self) -> int:
        return (self.dmodel // self.num_heads if self.head_size is None
                else self.head_size)

    @property
    def plain(self) -> bool:
        """K and V per head, as many key/value heads as query heads, heads
        of ``dmodel / num_heads`` and dense layers only: what
        ``LlamaConfig`` states, and the engine's original path."""
        return (self.attention is None and set(self.layer_kinds) == {"dense"}
                and self.num_kv_heads == self.num_heads
                and self.head_dim * self.num_heads == self.dmodel)

    @property
    def cache_row(self) -> int:
        """Values one cache position holds in one layer."""
        if self.attention is not None:
            return self.attention.row_dim
        return 2 * self.num_kv_heads * self.head_dim

    def runs(self) -> Tuple[Tuple[str, int, int], ...]:
        """The layers as runs of one kind: (kind, first layer, count)."""
        out, start = [], 0
        for i in range(1, self.n_layers + 1):
            if i == self.n_layers or self.layer_kinds[i] != self.layer_kinds[start]:
                out.append((self.layer_kinds[start], start, i - start))
                start = i
        return tuple(out)

    def replace(self, **kw) -> "ModelDescription":
        return dataclasses.replace(self, **kw)

    @classmethod
    def from_published(cls, cfg: dict, *, ctx_size: int, dtype: str,
                       param_dtype: str) -> "ModelDescription":
        """From the keys of a public ``config.json``, as
        ``benchmarks/configs/*.json`` carry them. Where the file states a
        chip's share, ``n_routed_experts`` counts the experts held here,
        ``first_held_expert`` says where the range starts and
        ``published["n_routed_experts"]`` is the router's width."""
        n = int(cfg["num_hidden_layers"])
        attention = experts = None
        kinds = ("dense",) * n
        if cfg.get("model_type") == "falcon_h1":
            return cls._falcon_h1(cfg, ctx_size, dtype, param_dtype)
        if "kv_lora_rank" in cfg:
            rs = cfg.get("rope_scaling") or {}
            attention = LatentAttention(
                q_rank=int(cfg["q_lora_rank"]),
                kv_rank=int(cfg["kv_lora_rank"]),
                nope_dim=int(cfg["qk_nope_head_dim"]),
                rope_dim=int(cfg["qk_rope_head_dim"]),
                v_dim=int(cfg["v_head_dim"]),
                rope_factor=float(rs.get("factor", 1.0)),
                rope_original_ctx=int(rs.get(
                    "original_max_position_embeddings", 4096)),
                beta_fast=float(rs.get("beta_fast", 32)),
                beta_slow=float(rs.get("beta_slow", 1)),
                mscale=float(rs.get("mscale", 1)),
                mscale_all_dim=float(rs.get("mscale_all_dim", 0)))
        elif cfg.get("num_key_value_heads",
                     cfg["num_attention_heads"]) != cfg["num_attention_heads"]:
            raise ValueError("grouped K/V heads: the engine has them beside "
                             "a state-space mixer only (model_type "
                             "falcon_h1)")
        if cfg.get("n_routed_experts"):
            held = int(cfg["n_routed_experts"])
            experts = ExpertLayer(
                n_experts=int(cfg.get("published", {}).get(
                    "n_routed_experts", held)),
                top_k=int(cfg["num_experts_per_tok"]),
                width=int(cfg["moe_intermediate_size"]),
                n_shared=int(cfg.get("n_shared_experts", 0)),
                scale=float(cfg.get("routed_scaling_factor", 1.0)),
                norm_topk=bool(cfg.get("norm_topk_prob", False)),
                held_start=int(cfg.get("first_held_expert", 0)),
                held_count=held)
            first = int(cfg.get("first_k_dense_replace", 0))
            freq = int(cfg.get("moe_layer_freq", 1))
            kinds = tuple("experts" if i >= first and i % freq == 0
                          else "dense" for i in range(n))
        return cls(vocab_size=int(cfg["vocab_size"]),
                   dmodel=int(cfg["hidden_size"]),
                   num_heads=int(cfg["num_attention_heads"]),
                   layer_kinds=kinds,
                   ffn_hidden=int(cfg["intermediate_size"]),
                   norm_eps=float(cfg["rms_norm_eps"]),
                   rope_theta=float(cfg["rope_theta"]),
                   ctx_size=ctx_size, dtype=dtype, param_dtype=param_dtype,
                   attention=attention, experts=experts,
                   init_std=float(cfg.get("initializer_range", 0.02)))

    @classmethod
    def _falcon_h1(cls, cfg: dict, ctx_size: int, dtype: str,
                   param_dtype: str) -> "ModelDescription":
        """``model_type`` ``falcon_h1``: every block a Mamba-2 mixer beside
        grouped-query attention (``head_dim`` stated, not ``hidden_size /
        num_attention_heads``), the muP multipliers by their keys.
        ``state_dtype`` (this repo's key, float32 unless stated) is the
        type of the carried state and of the recurrence."""
        if (cfg.get("attention_bias") or cfg.get("mlp_bias")
                or cfg.get("mamba_proj_bias") or cfg.get("projectors_bias")
                or not cfg.get("mamba_conv_bias", True)
                or not cfg.get("mamba_rms_norm", True)
                or cfg.get("mamba_norm_before_gate")
                or cfg.get("rope_scaling") or cfg.get("tie_word_embeddings")
                or cfg.get("attn_layer_indices") is not None):
            raise ValueError(
                "falcon_h1 as the engine has it: no projection biases, a "
                "convolution bias, the gated RMSNorm after the gate, plain "
                "RoPE, untied head, attention in every layer")
        mixer = StateSpaceMixer(
            d_inner=int(cfg["mamba_d_ssm"]), heads=int(cfg["mamba_n_heads"]),
            head_dim=int(cfg["mamba_d_head"]),
            groups=int(cfg["mamba_n_groups"]),
            state=int(cfg["mamba_d_state"]), conv=int(cfg["mamba_d_conv"]),
            chunk=int(cfg["mamba_chunk_size"]),
            state_dtype=str(cfg.get("state_dtype", "float32")))
        mult = Multipliers(
            embedding=float(cfg["embedding_multiplier"]),
            lm_head=float(cfg["lm_head_multiplier"]),
            attention_in=float(cfg["attention_in_multiplier"]),
            attention_out=float(cfg["attention_out_multiplier"]),
            key=float(cfg["key_multiplier"]),
            ssm_in=float(cfg["ssm_in_multiplier"]),
            ssm_out=float(cfg["ssm_out_multiplier"]),
            ssm=tuple(float(m) for m in cfg["ssm_multipliers"]),
            mlp=tuple(float(m) for m in cfg["mlp_multipliers"]))
        return cls(vocab_size=int(cfg["vocab_size"]),
                   dmodel=int(cfg["hidden_size"]),
                   num_heads=int(cfg["num_attention_heads"]),
                   layer_kinds=("parallel",) * int(cfg["num_hidden_layers"]),
                   ffn_hidden=int(cfg["intermediate_size"]),
                   norm_eps=float(cfg["rms_norm_eps"]),
                   rope_theta=float(cfg["rope_theta"]),
                   ctx_size=ctx_size, dtype=dtype, param_dtype=param_dtype,
                   init_std=float(cfg.get("initializer_range", 0.02)),
                   kv_heads=int(cfg["num_key_value_heads"]),
                   head_size=int(cfg["head_dim"]), mixer=mixer,
                   multipliers=mult)


def describe(cfg) -> ModelDescription:
    """The ``ModelDescription`` of a ``LlamaConfig`` (or ``cfg`` itself
    where it is one already)."""
    if isinstance(cfg, ModelDescription):
        return cfg
    return ModelDescription(
        vocab_size=cfg.vocab_size, dmodel=cfg.dmodel,
        num_heads=cfg.num_heads, layer_kinds=("dense",) * cfg.n_layers,
        ffn_hidden=cfg.ffn_dim, norm_eps=cfg.norm_eps,
        rope_theta=cfg.rope_theta, ctx_size=cfg.ctx_size, dtype=cfg.dtype,
        param_dtype=cfg.param_dtype)


@dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-Experts tiny-Llama configuration (parity-plus: the
    reference has no MoE/expert parallelism — SURVEY.md §2.10 marks EP
    "Absent"). Every block's SwiGLU MLP becomes a top-k routed expert bank;
    attention/embedding stay the LlamaConfig canonical shapes."""

    base: LlamaConfig = field(default_factory=LlamaConfig)
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25  # expert capacity = ceil(N·k/E · factor)
    aux_loss_coef: float = 0.01    # load-balance loss weight (Switch-style)

    def replace(self, **kw) -> "MoEConfig":
        return dataclasses.replace(self, **kw)


@dataclass(frozen=True)
class TrainConfig:
    """LLM training loop configuration (reference: primer/intro.py:22-23 —
    Adam lr 8e-4, 5000 iterations, batch 3 per rank, seq 256)."""

    batch_size: int = 3            # per-data-shard batch (reference: per-rank)
    seq_len: int = 256
    lr: float = 8e-4
    iters: int = 5000
    seed: int = 0
    # Mesh layout: named axis sizes. 1 disables that axis.
    data: int = 1
    # Hierarchical data parallelism: dcn > 1 splits the DP world into
    # ``dcn`` ICI islands of ``data`` replicas each (total world =
    # dcn·data; parallel/distributed.py:hier_data_mesh). Gradient sync
    # must then run the two-level ring driver (overlap_microbatches >= 1)
    # with per-axis wire formats: ``wire`` is the ICI tier's format
    # (fp32/bf16), ``wire_dcn`` the scarce DCN tier's (fp32/bf16/int8_ef)
    # — compression spent exactly where bandwidth is scarce.
    dcn: int = 1
    stage: int = 1                 # pipeline stages
    model: int = 1                 # tensor parallel degree
    seq: int = 1                   # sequence/context parallel degree
    microbatches: int = 1          # GPipe microbatches per step (PP)
    remat: bool = False            # jax.checkpoint on transformer blocks
    # Optimizer: "adam" (optax, the reference's), "fused" (ops/adam.py
    # single-pass), "pallas" (ops/pallas_adam.py fused apply), "master"
    # (ops/mixed_precision.py — pair with LlamaConfig param_dtype bf16).
    optimizer: str = "adam"
    # Gradient-allreduce wire format: "fp32" (plain pmean), "bf16" or
    # "int8_ef" (parallel/compress.py). On a hierarchical mesh (dcn > 1)
    # this is the ICI tier's format and ``wire_dcn`` selects the DCN
    # tier's. On the PP trainer a non-fp32 wire requires
    # overlap_microbatches >= 1 — it rides the DP×PP data-axis ring
    # (parallel/pp.py make_pipeline_overlap_*).
    wire: str = "fp32"
    # DCN-tier wire format of the two-level hierarchical collectives
    # (requires dcn > 1 and overlap_microbatches >= 1): "" defaults to
    # "fp32"; "int8_ef" is the headline mode — full-precision
    # reduce-scatter within each ICI island, int8+error-feedback across
    # the DCN hop only, intra-island gather after (the EQuARX/DynamiQ
    # shape; parallel/compress.py hier_reduce_scatter).
    wire_dcn: str = ""
    accum_steps: int = 1           # DP gradient accumulation (dp.py)
    # Fused multi-step dispatch (DP and PP trainers): K > 1 lax.scans K
    # training steps over a [K, B, T] device-resident batch window in ONE
    # compiled, donated dispatch (dp.make_multi_step /
    # make_zero1_multi_step; pp.make_pipeline_multi_step for any pipeline
    # schedule) — the per-step Python dispatch overhead is paid once per
    # window. Loss trajectory is bit-identical to K=1; host-side work
    # (loss sink, telemetry step events, checkpoint saves, StepGuard
    # verdicts, preempt checks) quantizes to chunk edges — see
    # train/llm.py:_run_loop.
    steps_per_dispatch: int = 1
    # Overlapped+compressed gradient sync (parallel/compress.py; on the
    # PP trainer the DP×PP data-axis version, parallel/pp.py
    # make_pipeline_overlap_*): M >= 1 routes gradient sync through the
    # ACCO-style microbatch ring driver — each step's local batch splits
    # into M
    # microbatches and microbatch k+1's grad compute overlaps microbatch
    # k's ppermute-pipelined ring reduce-scatter, with the in-flight
    # chunks in the ``wire`` format (fp32 / bf16 / int8+error-feedback,
    # EF residuals carried in the scan carry and the checkpointed state).
    # Composes with aggregation in {"gradient", "zero1"} and
    # steps_per_dispatch (bitwise-identical losses at any K for fixed M).
    # M = 1 is the no-split ring (compressed wire at zero1 composition,
    # no overlap); 0 disables — the legacy per-step paths run unchanged.
    # Wire bytes scale with M on the ring leg (each microbatch syncs), so
    # M > 1 trades wire for overlap — see docs/COMPONENTS.md's
    # composition matrix.
    overlap_microbatches: int = 0
    # Bucketed backward for the overlap drivers (compress.py BucketMap;
    # all three columns — DP, DP×PP, DP×TP — and the hierarchical
    # wire={"ici","dcn"} tier): B > 1 splits each microbatch's flat
    # gradient into B ordered buckets aligned to the stacked ``blocks``
    # layer groups, top-of-network first (VJP emission order), and each
    # bucket rings independently (labels ``*ring_grad_b{b}``) with no
    # data dependence on later buckets' grad compute — the within-
    # backward ACCO overlap (first ring hop starts before the full
    # gradient materializes; evidence via compress.ring_overlap_evidence,
    # gated in experiments/comm_wire_smoke.py). ZeRO-1 moments and EF
    # residuals become per-bucket tuples in the checkpointed state (the
    # reshard_state bucket contract). Total ring/gather payload bytes are
    # exactly invariant in B (the int8 ring adds one 4-byte scale per
    # extra bucket per hop); fp32 stays bitwise vs B=1 on
    # exact-arithmetic inputs. Requires overlap_microbatches >= 1;
    # 1 is the legacy single-vector ring.
    comm_buckets: int = 1
    # In-jit numerics summaries (telemetry/introspect.py; DP trainer
    # gradient/zero1, PP trainer via pp.make_pp_numerics with block
    # groups stage-qualified): N > 0 instruments the compiled step with
    # per-layer-group grad/param/update norms + per-leaf NaN attribution
    # and emits a ``numerics`` event every N steps (the emission syncs the
    # tiny summary arrays; the in-jit compute itself is free and
    # bitwise-invisible — losses/params identical on vs off, pinned in
    # tests/test_introspect.py and tests/test_pp.py). 0 disables
    # instrumentation entirely.
    numerics_every: int = 0
    # Partially-synchronized activations (TP trainer; parallel/tp.py,
    # after arXiv 2506.19645): how the per-sub-layer TP activation
    # all-reduces on the forward critical path are performed. "" — the
    # legacy Megatron path (raw in-model psum; the bitwise reference).
    # "full" — the SAME sync positions routed through the telemetry comm
    # wrappers: value-identical to "", but the model-axis activation wire
    # becomes visible to telemetry/comm.py (the smoke's same-run
    # baseline). "defer:L" — one boundary sync per L layers instead of
    # two per layer (requires n_layers % L == 0); activations between
    # boundaries evolve from per-shard partial sums, cutting model-axis
    # activation wire to 1/(2L) of full sync at a pinned
    # convergence-tolerance cost. "int8_ef" — every sub-layer sync is an
    # int8 all-gather with a per-(model-shard, sub-layer) error-feedback
    # residual tree carried in the train state (compress.py's EF shape),
    # ~tp/8 of full-sync wire; gradients flow as if the sync were an
    # exact psum. Relaxed modes hold the convergence bars pinned in
    # tests/test_tp.py; wire budgets are gated in
    # experiments/tp_fusion_smoke.py.
    psa: str = ""


@dataclass(frozen=True)
class ResilienceConfig:
    """Self-healing knobs for the training loops (resilience/).

    Passed to the LLM trainers (``resilience=``) and honored by bench /
    experiment drivers. ``faults`` is a FaultPlan spec string (see
    resilience/faults.py) so injection runs are configurable from a CLI
    flag; empty means inject nothing. Defaults are the production posture:
    guard on, detector warmed up past optimizer-startup transients.
    """

    guard: bool = True             # wrap the train step in a StepGuard
    # In-jit non-finite skip fused INTO the compiled step (gradient/zero1
    # and the overlap/ring drivers, parallel/{dp,compress}.py
    # ``guard_nonfinite``): a bad step select-backs the whole state —
    # EF residuals included — without leaving jit, the step counter does
    # not advance, and the loop counts the non-advances into
    # ``ResilienceStats.skipped_steps`` at the end-of-run sync. Mutually
    # exclusive with ``guard`` (the host-side StepGuard would double-count
    # the same skip; pick the sync-free fused skip OR the host guard's
    # EMA/rollback machinery).
    injit_guard: bool = False
    max_consecutive_bad: int = 3   # K consecutive bad steps → rollback
    ema_decay: float = 0.98        # update-norm EMA smoothing
    anomaly_factor: float = 10.0   # spike threshold (×EMA); <=0 disables
    ema_warmup: int = 20           # good steps before the detector arms
    retry_attempts: int = 3        # checkpoint-IO retry budget
    retry_base_delay: float = 0.1  # seconds; doubles per attempt, jittered
    faults: str = ""               # FaultPlan spec for injection runs
    fault_seed: int = 0            # drives every random fault choice
    # Elastic parallelism (resilience/elastic.py; DP, DP×PP, and DP×TP
    # fused-dispatch trainers): survive device loss mid-run by draining
    # at the chunk edge, re-meshing onto the survivors and resharding
    # the state. On a DP×PP mesh the controller prefers dropping a data
    # row; when the victim's stage column has no surviving replica it
    # RE-PARTITIONS layers onto fewer stages (S→S′, S′ | n_layers) and
    # re-slices the stage-sharded state by global coordinate id. On
    # DP×TP only the data axis re-meshes (PSA activation EF residuals
    # resize per data row); a model-axis loss is unrecoverable. With
    # zero faults the elastic loop's loss trajectory is bitwise the
    # non-elastic one (tests/test_elastic.py).
    elastic: bool = False
    # Host-RAM last-good state mirror cadence, in chunk edges: 1 mirrors
    # every edge (recovery replays nothing), k mirrors every k-th (cheaper
    # steady state, up to k·steps_per_dispatch steps replayed on
    # recovery), 0 disables the fast path (recovery goes through the
    # checkpoint).
    mirror_every: int = 1

    def fault_plan(self):
        """The configured FaultPlan (empty spec → empty plan)."""
        from .resilience.faults import FaultPlan
        return FaultPlan.from_spec(self.faults, seed=self.fault_seed)


@dataclass(frozen=True)
class VFLConfig:
    """Vertical FL / split learning configuration (reference:
    lab/tutorial_2b/vfl.py:159-168 — 4 clients, 300 epochs, batch 64)."""

    nr_clients: int = 4
    epochs: int = 300
    batch_size: int = 64
    lr: float = 1e-3
    # Per-client bottom output width multiplier: party i sends
    # bottom_out_mult · d_i activations up the cut — the reference's
    # outs_per_client sizing (vfl.py:139-141).
    bottom_out_mult: int = 2
    seed: int = 0


@dataclass(frozen=True)
class VAEConfig:
    """Tabular VAE configuration (reference: lab/tutorial_2a/
    generative-modeling.py:13-116 — input 13, latent dim 3, BN-MLP stack)."""

    input_dim: int = 13
    hidden_dims: Tuple[int, ...] = (50, 12)
    latent_dim: int = 3
    lr: float = 1e-3
    epochs: int = 200
    batch_size: int = 64
    seed: int = 0


@dataclass(frozen=True)
class AttackConfig:
    """Byzantine adversary injection (reference: lab/tutorial_3/
    attacks_and_defenses.ipynb cell 9 — 20% malicious, and the hw03 sweep
    setting lr=0.02, B=200, C=0.2, E=2, seed 42)."""

    malicious_fraction: float = 0.2
    attack: str = "gradient_reversion"
    scale: float = 5.0             # the -5x / 5x / 2x update scaling knobs
    backdoor_label: int = 0
    seed: int = 42
