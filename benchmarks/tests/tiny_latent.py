"""`tiny.py`'s checkout with the latent-attention, routed-expert
configuration shrunk further, for the CPU: `tiny.py` shrinks the keys every
configuration has (hidden size, heads, depth, vocabulary), this the keys only
such a model has (ranks, head sizes, expert width and count), and gives the
cell a prefill chunk long enough that the chunk expands the rows while the
decode step folds them."""
import json
import os

import tiny

CONFIG = "a.x-k1.json"
TRAFFIC = "docs_backlog.json"
CELL = "serve_axk1_docs_backlog"

TINY_LATENT = dict(q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
                   qk_rope_head_dim=8, v_head_dim=16,
                   moe_intermediate_size=32, num_hidden_layers=3,
                   num_attention_heads=4, num_key_value_heads=4,
                   first_held_expert=12,
                   # width 64 in place of 7168: the same gain a layer, so
                   # that a fault in a layer shows in the logits as it would
                   initializer_range=0.2)


def make_tiny_checkout(dest: str) -> str:
    tiny.make_tiny_checkout(dest)
    bdir = os.path.join(dest, "benchmarks")
    path = os.path.join(bdir, "configs", CONFIG)
    with open(path) as f:
        cfg = json.load(f)
    cfg.update(TINY_LATENT)
    cfg["published"] = dict(cfg["published"], n_routed_experts=24)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"],
                               original_max_position_embeddings=16)
    with open(path, "w") as f:
        json.dump(cfg, f)
    tiny._edit(os.path.join(bdir, "traffic", TRAFFIC), prefill_chunk=40)
    return dest


def make_exact(dest: str) -> None:
    """Compute and cache in float32 (the weights stay the seed's bf16
    values): the program then reads within 1e-3 of the reference, and a fault
    as small as one expert's output shows."""
    tiny._edit(os.path.join(dest, "benchmarks", "configs", CONFIG),
               compute_dtype="float32", cache_dtype="float32")


def write_tiny_limits(dest: str, limit: float) -> None:
    with open(os.path.join(dest, "benchmarks", "limits", CELL + ".json"),
              "w") as f:
        json.dump({"served_logit_gap": limit}, f)
