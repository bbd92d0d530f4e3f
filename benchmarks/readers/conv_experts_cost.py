"""Operations and bytes that serving a decoder of short convolutions and
grouped-query attention, a mixer a layer, with routed experts requires, from
the shapes and the program's counters alone
(`references/conv_experts.py::Dims`): the same work whatever implements it.

A multiply-add is 2 operations. Per layer a token passes through its mixer's
weights (`conv_params`: W_in d x 3d, the taps, W_out d x d; or
`attention_params`: W_qkv d x (Hq + 2 Hkv) Dh, the q/k norms, W_o Hq Dh x d)
and, in a dense layer, 3 d f; in an expert layer, the router and its bias
(d E + E) and one expert (3 d w) a token-expert PAIR, which the program
counts (`pairs_held`). Attention is 4 Dh Hq operations a query-key pair a
layer (QK^T and PV over the query heads); the convolution's taps and gates
are a few operations a value and are not counted. Norms are left out.

Bytes: a cache position holds K and V of the key/value heads of every
attention layer (`kv_bytes_per_position`); a slot's tails are `taps - 1`
values of `B * x` a conv layer (`tail_bytes_per_slot`), read and written
once a step. The head is the embedding's transpose: read once a step.
"""

from __future__ import annotations


def attention_layers(dims) -> int:
    return dims.mixers.count("attention")


def conv_layers(dims) -> int:
    return dims.mixers.count("conv")


def expert_layers(dims) -> int:
    return dims.layers - dims.n_dense


def conv_params(dims) -> int:
    return 3 * dims.d * dims.d + dims.taps * dims.d + dims.d * dims.d


def attention_params(dims) -> int:
    return (dims.d * dims.qkv_dim + dims.heads * dims.head_dim * dims.d
            + 2 * dims.head_dim)


def router_params(dims) -> int:
    return dims.d * dims.experts + dims.experts


def expert_params(dims) -> int:
    """One routed expert: gate, up and down."""
    return 3 * dims.d * dims.width


def outside_experts_params(dims) -> int:
    """Every weight of the layers but the routed experts (norms left out)."""
    return (conv_layers(dims) * conv_params(dims)
            + attention_layers(dims) * attention_params(dims)
            + dims.n_dense * 3 * dims.d * dims.ffn
            + expert_layers(dims) * router_params(dims))


def pair_flops(dims) -> int:
    """A query-key pair in one attention layer, over the query heads."""
    return 4 * dims.head_dim * dims.heads


def kv_bytes_per_position(dims, itemsize: int = 2) -> int:
    return (attention_layers(dims) * 2 * dims.kv_heads * dims.head_dim
            * itemsize)


def tail_bytes_per_slot(dims, itemsize: int = 2) -> int:
    return conv_layers(dims) * (dims.taps - 1) * dims.d * itemsize


def serve_flops(dims, tokens_processed: int, context_sum: int, sampled: int,
                pairs_held: int) -> float:
    """Model operations of serving: 2 a weight outside the experts a token
    processed, the experts by the pairs computed, attention over the context
    each token attends to in every attention layer, and the head where a
    token is sampled."""
    return (2.0 * outside_experts_params(dims) * tokens_processed
            + 2.0 * expert_params(dims) * pairs_held
            + float(pair_flops(dims)) * attention_layers(dims) * context_sum
            + 2.0 * dims.d * dims.vocab * sampled)


def paged_cost(dims, live_positions: int, itemsize: int = 2) -> dict:
    """The least one decode step's attention over the pool has to do: read
    K and V of every live position in every attention layer once."""
    return {"flops": float(pair_flops(dims)) * attention_layers(dims)
            * live_positions,
            "bytes": float(live_positions * kv_bytes_per_position(
                dims, itemsize))}


def experts_cost(dims, tokens: int, pairs_held: int, experts_hit: int,
                 itemsize: int = 2) -> dict:
    """The least the expert layers of one step have to do for `tokens`
    tokens: read the router of every expert layer and each expert hit
    (`experts_hit`, over all layers), 2 operations a router weight a token
    and an expert's weight a pair."""
    n = expert_layers(dims)
    return {"flops": 2.0 * router_params(dims) * n * tokens
            + 2.0 * expert_params(dims) * pairs_held,
            "bytes": float((router_params(dims) * n
                            + expert_params(dims) * experts_hit) * itemsize)}


def decode_step_cost(dims, live_positions: int, active: int, pairs_held: int,
                     experts_hit: int, state_slots: int,
                     itemsize: int = 2) -> dict:
    """The least one decode step has to do: read every weight outside the
    experts and the head once, the experts hit, the embedding rows looked
    up, the live cache positions, and read and write the decoding slots'
    tails; 2 operations a weight a token, the pairs' experts, and attention
    over the live positions."""
    weights = outside_experts_params(dims) + dims.d * dims.vocab
    att = paged_cost(dims, live_positions, itemsize)
    return {"flops": 2.0 * weights * active
            + 2.0 * expert_params(dims) * pairs_held + att["flops"],
            "bytes": float((weights + expert_params(dims) * experts_hit
                            + active * dims.d) * itemsize
                           + 2 * state_slots * tail_bytes_per_slot(
                               dims, itemsize)) + att["bytes"]}
