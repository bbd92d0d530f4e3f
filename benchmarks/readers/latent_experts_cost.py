"""Operations and bytes that serving a latent-attention decoder with routed
experts requires, from the shapes and the program's counters alone
(`references/latent_experts.py::Dims`): the same work whatever implements it.

A multiply-add is 2 operations. Per layer a token passes through the
attention's weights (`attention_params`: W_qa, W_qb, W_kva, W_kvb, W_o) and,
in the dense layer, 3 d f; in an expert layer, the router (d E), the shared
experts (3 d w each) and one expert (3 d w) a token-expert PAIR COMPUTED
HERE, which the program counts (`pairs_held`): pairs routed to experts held
elsewhere cost this chip nothing. Attention is counted in its expanded form,
2 H (qk + v) operations a query-key pair a layer, whichever form the program
runs; only `latent_attention_cost`, the least a decode step's attention over
the cache can take, counts the folded form, 2 H (row + kv_rank) a position,
because a step that does not expand the cache has to fold. Bytes of weights
are what a step must read: everything outside the routed experts once, and
one expert's 3 d w a held expert HIT (`experts_hit`, summed over layers).
"""

from __future__ import annotations


def attention_params(dims) -> int:
    qk = dims.nope + dims.rope
    return (dims.d * dims.q_rank + dims.q_rank * dims.heads * qk
            + dims.d * (dims.kv_rank + dims.rope)
            + dims.kv_rank * dims.heads * (dims.nope + dims.v)
            + dims.heads * dims.v * dims.d)


def expert_params(dims) -> int:
    """One routed (or shared) expert: gate, up and down."""
    return 3 * dims.d * dims.width


def expert_layers(dims) -> int:
    return dims.layers - dims.first_dense


def outside_experts_params(dims) -> int:
    """Every weight of the layers but the routed experts (norms left out)."""
    return (dims.layers * attention_params(dims)
            + dims.first_dense * 3 * dims.d * dims.ffn
            + expert_layers(dims) * (dims.d * dims.experts
                                     + dims.shared * expert_params(dims)))


def row_dim(dims) -> int:
    """Values a cache position holds in one layer."""
    return dims.kv_rank + dims.rope


def pair_flops_expanded(dims) -> int:
    """A query-key pair in one layer, keys and values per head."""
    return 2 * dims.heads * (dims.nope + dims.rope + dims.v)


def pair_flops_folded(dims) -> int:
    """A query-key pair in one layer over the latent row itself."""
    return 2 * dims.heads * (row_dim(dims) + dims.kv_rank)


def serve_flops(dims, tokens_processed: int, context_sum: int, sampled: int,
                pairs_held: int) -> float:
    """Model operations of serving: 2 a weight used a token processed, the
    routed experts by the pairs computed here, attention at the expanded
    count over the context each token attends to, and the head where a
    token is sampled."""
    return (2.0 * outside_experts_params(dims) * tokens_processed
            + 2.0 * expert_params(dims) * pairs_held
            + float(pair_flops_expanded(dims)) * dims.layers * context_sum
            + 2.0 * dims.d * dims.vocab * sampled)


def latent_attention_cost(dims, live_positions: int, itemsize: int = 2) -> dict:
    """The least one decode step's attention over the cache has to do: read
    the latent row of every live position in every layer once, and a folded
    query-key and probability-value product over all heads."""
    return {"flops": float(pair_flops_folded(dims)) * dims.layers
            * live_positions,
            "bytes": float(live_positions * row_dim(dims) * itemsize
                           * dims.layers)}


def expert_layers_cost(dims, tokens: int, pairs_held: int, experts_hit: int,
                       itemsize: int = 2) -> dict:
    """The least the expert layers of one step have to do for `tokens`
    tokens: read the router and the shared experts of every expert layer
    and the experts hit (`experts_hit`, over all layers), and 2 operations
    a weight a token (router, shared) or a pair (routed)."""
    fixed = dims.d * dims.experts + dims.shared * expert_params(dims)
    n = expert_layers(dims)
    return {"flops": 2.0 * fixed * n * tokens
            + 2.0 * expert_params(dims) * pairs_held,
            "bytes": float((fixed * n + expert_params(dims) * experts_hit)
                           * itemsize)}


def decode_step_cost(dims, live_positions: int, active: int, pairs_held: int,
                     experts_hit: int, itemsize: int = 2) -> dict:
    """The least one decode step has to do: read every weight outside the
    routed experts and the head once, the experts hit, the embedding rows
    looked up and the live latent rows; 2 operations a weight a token, the
    pairs' experts, and folded attention over the live positions."""
    weights = (outside_experts_params(dims) + dims.d * dims.vocab
               + expert_params(dims) * experts_hit + active * dims.d)
    att = latent_attention_cost(dims, live_positions, itemsize)
    return {"flops": 2.0 * (outside_experts_params(dims)
                            + dims.d * dims.vocab) * active
            + 2.0 * expert_params(dims) * pairs_held + att["flops"],
            "bytes": float(weights * itemsize) + att["bytes"]}
