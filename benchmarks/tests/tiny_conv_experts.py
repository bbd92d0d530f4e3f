"""`tiny.py`'s checkout with the short-convolution configuration shrunk
further, for the CPU: `tiny.py` shrinks the keys every configuration has
(hidden size, heads, depth, vocabulary), this the keys only such a model has
(the layer types, the experts) and gives it heads of 64, as published (two to
a row of the pool), 2 query heads a key/value head, the top 4 of 16 experts,
two leading dense layers and one whole period of (attention, conv, conv,
conv) with experts, then what is left of one. The initialisation is wider
than the published widths take, so that a fault in a layer shows in the
logits as it would there."""
import json
import os

import tiny

CONFIG = "lfm2-24b-a2b.json"
CELL = "serve_lfm2_chat_backlog"

TINY_LAYERS = dict(hidden_size=256, intermediate_size=96,
                   num_attention_heads=4, num_key_value_heads=2,
                   num_hidden_layers=8, moe_intermediate_size=32,
                   num_experts=16, initializer_range=0.2)


def make_tiny_checkout(dest: str) -> str:
    tiny.make_tiny_checkout(dest)
    tiny._edit(os.path.join(dest, "benchmarks", "configs", CONFIG),
               **TINY_LAYERS)
    return dest


def make_exact(dest: str) -> None:
    """Compute and cache in float32 (the weights stay the seed's bf16
    values): the program then reads within 1e-3 of the reference, and a fault
    shows."""
    tiny._edit(os.path.join(dest, "benchmarks", "configs", CONFIG),
               compute_dtype="float32", cache_dtype="float32")


def write_tiny_limits(dest: str, limit: float) -> None:
    with open(os.path.join(dest, "benchmarks", "limits", CELL + ".json"),
              "w") as f:
        json.dump({"served_logit_gap": limit}, f)
