"""Operations and bytes that serving a decoder of state-space mixers beside
grouped-query attention requires, from the shapes and the program's counters
alone (`references/state_space.py::Dims`): the same work whatever implements
it.

A multiply-add is 2 operations. Per layer a token passes through the mixer's
weights (`mixer_params`: W_in d x proj_dim, W_out d_inner x d, the
convolution, dt_bias, A, D, the gated norm), the attention's (`W_qkv` d x
(Hq + 2 Hkv) Dh, `W_o` Hq Dh x d) and the SwiGLU's 3 d f. Attention is 4 Dh
Hq operations a query-key pair a layer (QK^T and PV over the query heads).
The recurrence, as a recurrence, is 4 H P N operations a token a layer: the
state's update `S = decay S + dt x (outer) B` and its read `y = S C`, a
multiply-add an element each (`scan_flops_per_token`). As the chunked scan
over chunks of Q positions it is, a chunk a layer, `2 Q^2 (G N + H P)` for
the masked product within the chunk and `4 Q H P N` for the chunk's
contribution to the state and the state's contribution to the chunk
(`chunk_scan_flops`): more operations than the recurrence, all of them
matrix products.

Bytes: a cache position holds K and V of the key/value heads in every layer
(`kv_bytes_per_position`); a slot's state is H P N values in the state's type
and conv - 1 rows of the convolution's inputs in the compute type, a layer
(`state_bytes_per_slot`), read and written once a step.
"""

from __future__ import annotations


def mixer_params(dims) -> int:
    return (dims.d * dims.proj_dim + dims.d_inner * dims.d
            + (dims.conv + 1) * dims.conv_dim + 3 * dims.ssm_heads
            + dims.d_inner)


def attention_params(dims) -> int:
    return dims.d * dims.qkv_dim + dims.heads * dims.head_dim * dims.d


def layer_params(dims) -> int:
    """Every weight of a layer (its two norms of d left out)."""
    return mixer_params(dims) + attention_params(dims) + 3 * dims.d * dims.ffn


def pair_flops(dims) -> int:
    """A query-key pair in one layer, over the query heads."""
    return 4 * dims.head_dim * dims.heads


def scan_flops_per_token(dims) -> int:
    """The recurrence's update and read of the state, one layer."""
    return 4 * dims.ssm_heads * dims.ssm_head_dim * dims.state


def chunk_scan_flops(dims) -> int:
    """The chunked scan over one chunk of `dims.chunk` positions, one layer."""
    q, hp = dims.chunk, dims.ssm_heads * dims.ssm_head_dim
    return (2 * q * q * (dims.groups * dims.state + hp)
            + 4 * q * hp * dims.state)


def kv_bytes_per_position(dims, itemsize: int = 2) -> int:
    return 2 * dims.kv_heads * dims.head_dim * itemsize * dims.layers


def state_bytes_per_slot(dims, state_itemsize: int = 4,
                         itemsize: int = 2) -> int:
    """The recurrent state and the convolution's carried inputs of one slot
    over all layers."""
    return dims.layers * (
        dims.ssm_heads * dims.ssm_head_dim * dims.state * state_itemsize
        + (dims.conv - 1) * dims.conv_dim * itemsize)


def serve_flops(dims, tokens_processed: int, context_sum: int, sampled: int,
                scan_tokens: int) -> float:
    """Model operations of serving: 2 a layer weight a token processed,
    attention over the context each token attends to, the recurrence a token
    the program's counters say passed through it, and the head where a token
    is sampled."""
    return (2.0 * dims.layers * layer_params(dims) * tokens_processed
            + float(pair_flops(dims)) * dims.layers * context_sum
            + float(scan_flops_per_token(dims)) * dims.layers * scan_tokens
            + 2.0 * dims.d * dims.vocab * sampled)


def mixer_step_cost(dims, state_slots: int, itemsize: int = 2,
                    state_itemsize: int = 4) -> dict:
    """The least the mixers of one decode step have to do: read their
    weights once, read and write the state and the carried inputs of the
    `state_slots` slots that decode, 2 operations a weight a token and the
    recurrence."""
    n = dims.layers
    return {"flops": (2.0 * mixer_params(dims)
                      + scan_flops_per_token(dims)) * n * state_slots,
            "bytes": float(n * mixer_params(dims) * itemsize
                           + 2 * state_slots * state_bytes_per_slot(
                               dims, state_itemsize, itemsize))}


def mixer_chunk_cost(dims, tokens: int, scan_chunks: int, itemsize: int = 2,
                     state_itemsize: int = 4) -> dict:
    """The least the mixers of one prefill chunk have to do for `tokens`
    real positions in `scan_chunks` chunks of the scan: the weights once and
    one slot's state in and out; 2 operations a weight a token and the
    chunked scan of the chunks that hold a real position."""
    n = dims.layers
    return {"flops": (2.0 * mixer_params(dims) * tokens
                      + float(chunk_scan_flops(dims)) * scan_chunks) * n,
            "bytes": float(n * mixer_params(dims) * itemsize
                           + 2 * state_bytes_per_slot(dims, state_itemsize,
                                                      itemsize))}


def decode_step_cost(dims, live_positions: int, active: int, state_slots: int,
                     itemsize: int = 2, state_itemsize: int = 4) -> dict:
    """The least one decode step has to do: read every weight of the layers
    and the head once and the embedding rows looked up, read and write the
    decoding slots' state, read the live cache positions; 2 operations a
    weight a token, the recurrence, and attention over the live positions."""
    weights = dims.layers * layer_params(dims) + dims.d * dims.vocab
    return {"flops": 2.0 * weights * active
            + float(scan_flops_per_token(dims)) * dims.layers * state_slots
            + float(pair_flops(dims)) * dims.layers * live_positions,
            "bytes": float((weights + active * dims.d) * itemsize
                           + 2 * state_slots * state_bytes_per_slot(
                               dims, state_itemsize, itemsize)
                           + live_positions * kv_bytes_per_position(
                               dims, itemsize))}
