"""`tiny.py`'s checkout with the state-space configuration shrunk further,
for the CPU: `tiny.py` shrinks the keys every configuration has (hidden size,
heads, depth, vocabulary), this the keys only such a model has (the mixer's
sizes, the head size, the grouping) and the multipliers, which are set for
the small widths as muP sets the published ones for theirs: large enough that
the state-space branch is a visible share of a logit. It keeps 5 query heads a
key/value head, 2 groups, a head size that is not `hidden / heads`, and gives
the cell a prefill chunk of two chunks of the scan."""
import json
import os

import tiny

CONFIG = "falcon-h1-34b.json"
TRAFFIC = "chat_short_backlog.json"
CELL = "serve_falconh1_chat_backlog"

TINY_MIXER = dict(hidden_size=96, intermediate_size=160,
                  num_attention_heads=10, num_key_value_heads=2, head_dim=8,
                  num_hidden_layers=3, mamba_d_ssm=64, mamba_n_heads=8,
                  mamba_d_head=8, mamba_d_state=16, mamba_chunk_size=8,
                  lm_head_multiplier=0.125, attention_out_multiplier=0.3,
                  key_multiplier=0.33, ssm_in_multiplier=1.0,
                  ssm_out_multiplier=1.5,
                  ssm_multipliers=[0.7, 1.0, 1.5, 2.0, 1.2],
                  mlp_multipliers=[0.7, 0.18],
                  # the same gain a layer as at width 5120, so that a fault
                  # in a layer shows in the logits as it would
                  initializer_range=0.2)


def make_tiny_checkout(dest: str) -> str:
    tiny.make_tiny_checkout(dest)
    bdir = os.path.join(dest, "benchmarks")
    tiny._edit(os.path.join(bdir, "configs", CONFIG), **TINY_MIXER)
    return dest


def make_exact(dest: str) -> None:
    """Compute and cache in float32 (the weights stay the seed's bf16
    values): the program then reads within 1e-3 of the reference, and a fault
    in one chunk's carried state shows."""
    tiny._edit(os.path.join(dest, "benchmarks", "configs", CONFIG),
               compute_dtype="float32", cache_dtype="float32")


def write_tiny_limits(dest: str, limit: float) -> None:
    with open(os.path.join(dest, "benchmarks", "limits", CELL + ".json"),
              "w") as f:
        json.dump({"served_logit_gap": limit}, f)
