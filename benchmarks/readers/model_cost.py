"""Operations and bytes that the work requires, from the shapes alone.

Every count is of what the mathematics needs, whatever implements it:
recomputed operations (`remat`) are not counted, and attention is counted
causally: a query at position t attends to t+1 keys, so a sequence of T
tokens needs T(T+1)/2 query-key pairs, about half of what `bench.py`'s
`train_step_flops_per_token` counts (4*T*d a layer, no mask), which
overstates a utilisation.

Per layer a token passes through 4 d^2 (q, k, v, o) + 3 d f (gate, up,
down) weights; the head has d V. A multiply-add is 2 operations.
"""

from __future__ import annotations


def layer_params(d: int, ffn: int) -> int:
    return 4 * d * d + 3 * d * ffn


def model_params(dims) -> int:
    """Weights of the layers, the embedding and the head (norms left out:
    they are 2 d a layer)."""
    return dims.layers * layer_params(dims.d, dims.ffn) + 2 * dims.d * dims.vocab


def attention_pairs_causal(t: int) -> int:
    """Query-key pairs of one causal sequence of t tokens."""
    return t * (t + 1) // 2


def attention_flops_fwd(t: int, d: int) -> int:
    """Forward operations of causal attention over one sequence of t tokens
    in one layer: QK^T and PV, 2*d multiply-adds a pair over all heads."""
    return 4 * d * attention_pairs_causal(t)


def train_flops_per_token(dims, seq_len: int) -> float:
    """Forward and backward (3x forward) operations a token of a sequence of
    `seq_len`: 6 a weight of the layers and the head (the embedding is a
    gather), and causal attention 3 * 4*d*(T+1)/2 a layer."""
    weights = dims.layers * layer_params(dims.d, dims.ffn) + dims.d * dims.vocab
    attn = dims.layers * 3 * attention_flops_fwd(seq_len, dims.d) / seq_len
    return 6.0 * weights + attn


def flash_train_cost(dims, batch: int, seq_len: int) -> dict:
    """One training step's causal attention over all layers, forward and
    backward, as the flash kernels have to do it. Operations a query-key
    pair, over all heads: forward QK^T and PV, 4*d; backward dV, dP, dQ and
    dK, 8*d (the scores the kernel computes again are recomputation and are
    not counted). Bytes: forward reads q, k, v and writes o; backward reads
    q, k, v, o, do and writes dq, dk, dv; 12 arrays of batch*T*d elements a
    layer in the compute type (2 bytes)."""
    pairs = batch * dims.layers * attention_pairs_causal(seq_len)
    elems = batch * dims.layers * seq_len * dims.d
    return {"flops": float(12 * dims.d * pairs),
            "bytes": float(12 * elems * 2)}


def kv_bytes_per_position(dims, itemsize: int = 2) -> int:
    """K and V of one cache position over all layers."""
    return 2 * dims.layers * dims.d * itemsize


def decode_step_cost(dims, live_positions: int, active: int,
                     itemsize: int = 2) -> dict:
    """The least one decode step has to do: read every weight of the layers
    and the head once, plus the embedding rows it looks up, read the live
    cache positions of the active slots, and 2 operations a weight a token
    plus attention over the live positions."""
    weights = dims.layers * layer_params(dims.d, dims.ffn) + dims.d * dims.vocab
    nbytes = weights * itemsize + live_positions * kv_bytes_per_position(
        dims, itemsize)
    flops = 2.0 * weights * active + 4.0 * dims.d * dims.layers * live_positions
    return {"flops": flops, "bytes": float(nbytes)}


def serve_flops(dims, tokens_processed: int, context_sum: int,
                sampled: int) -> float:
    """Model operations of serving: 2 a layer weight a token processed
    (prompt or output), attention 4*d a layer over the context each such
    token attends to (`context_sum` is the sum of those context lengths),
    and the head where a token is sampled."""
    return (2.0 * dims.layers * layer_params(dims.d, dims.ffn) * tokens_processed
            + 4.0 * dims.d * dims.layers * context_sum
            + 2.0 * dims.d * dims.vocab * sampled)
