"""Rehearsal of the cell that serves a decoder of short convolutions and
grouped-query attention, a mixer a layer, with routed experts, on the CPU at
tiny sizes, and unit tests of its cost functions.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_conv_experts_cell.py -q -p no:cacheprovider

`test_rehearsal.py` already runs the cell with only the keys `tiny.py` knows
shrunk: two layers, the two leading dense conv layers, so no attention layer
and no pool. Here the keys only this model has are shrunk too
(`tiny_conv_experts.py`): eight layers, two dense and a whole period of
(attention, conv, conv, conv) with experts, then (attention, conv); heads of
64. The faults are planted in a checkout that computes in float32, where the
sound program reads 0 and each fault 0.003 and more: the cell's number is a
mean over the served positions, which a fault that moves a few of them
moves little.
"""

import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [HERE, BENCH, os.path.join(BENCH, "readers")]

import tiny_conv_experts  # noqa: E402
from test_rehearsal import CONTRACT_KEYS, cells, drive  # noqa: E402

CELL = tiny_conv_experts.CELL


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """bf16 against float32 at these widths, with the initialisation widened
    as `tiny_conv_experts.py` says, reads a mean gap of up to about 0.05 on
    the served tokens: the tiny size's limit is set above that."""
    dest = tiny_conv_experts.make_tiny_checkout(
        str(tmp_path_factory.mktemp("conv")))
    tiny_conv_experts.write_tiny_limits(dest, 1.0)
    return dest


@pytest.fixture(scope="module")
def exact(tmp_path_factory):
    dest = tiny_conv_experts.make_tiny_checkout(
        str(tmp_path_factory.mktemp("exact")))
    tiny_conv_experts.make_exact(dest)
    tiny_conv_experts.write_tiny_limits(dest, 0.001)
    return dest


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_runs_with_attention_and_experts(checkout, trace):
    last, proc = drive(checkout, CELL, 2 ** 31 + 41 + trace, 2, trace)
    assert CONTRACT_KEYS <= set(last) and last["correct"] is True, \
        proc.stderr[-2000:]
    assert last["attempted"] > 0 and last["failed"] == 0
    assert last["checks"]["queue_empty_before_close"]["value"] == 0
    want = {m["name"] for m in cells()["end_to_end" if not trace
                                       else "per_layer"]
            if CELL in m.get("workloads", [CELL])}
    if trace:
        want -= {m["name"] for m in cells()["per_layer"]
                 if m["source"] == "device_trace"}
        assert "step_mfu.serve_lfm2" in want
    assert set(last["metrics"]) == want
    assert all(m["value"] > 0 for m in last["metrics"].values())


@pytest.mark.parametrize("fault", ["tail_dropped", "bias_ignored",
                                   "bias_weighted", "qk_norm_skipped",
                                   "group_misread", "token_altered"])
def test_a_planted_fault_comes_out_not_correct(exact, fault):
    """A second chunk that starts from a zero tail; the experts chosen by
    the scores alone; the bias in the weights too; queries and keys not
    normed a head; query heads that read key/value head `i % 2`; a token
    altered where it is produced."""
    sound, _ = drive(exact, CELL, 21, 1, 0)
    assert sound["correct"] is True, sound["checks"]
    broken, _ = drive(exact, CELL, 21, 1, 0, fault)
    assert broken["correct"] is False
    assert broken["checks"]["served_logit_gap"]["ok"] is False


def test_an_unknown_fault_is_refused(exact):
    import subprocess

    from test_rehearsal import ENV
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "drive.py"), exact, CELL, "1",
         "1", "0", "no_such_fault"], env=ENV, capture_output=True, text=True,
        timeout=600)
    assert proc.returncode != 0 and "unknown fault" in proc.stderr


def test_the_fp8_control_reads_wider_gaps_than_the_reference():
    import jax.numpy as jnp
    import numpy as np
    from references import conv_experts as ref

    with open(os.path.join(BENCH, "configs", tiny_conv_experts.CONFIG)) as f:
        cfg = json.load(f)
    cfg.update(tiny_conv_experts.TINY_LAYERS, vocab_size=512)
    dims = ref.Dims.from_config(cfg)
    toks = jnp.asarray(np.random.default_rng(1).integers(0, 512, 96),
                       jnp.int32)
    model = ref.Seeded(3, dims, "bfloat16")
    low = ref.Seeded(3, dims, "bfloat16", ref.CONTROL)
    own = ref.first_choice(model, toks)
    assert float(ref.gap_below_best(model, toks, own).max()) == 0.0
    assert float(ref.gap_below_best(
        model, toks, ref.first_choice(low, toks)).max()) > 0.05


# ------------------------------------------------------- the cost functions

def published_dims():
    from references import conv_experts as ref

    with open(os.path.join(BENCH, "configs", tiny_conv_experts.CONFIG)) as f:
        return ref.Dims.from_config(json.load(f))


def test_the_costs_against_hand_counts():
    import conv_experts_cost as cost

    d = published_dims()
    # W_in 2048 x 6144, the taps 3 x 2048, W_out 2048 x 2048
    assert cost.conv_params(d) == 12_582_912 + 6_144 + 4_194_304
    # q 2048 x 2048, k and v 2048 x 512 each, o 2048 x 2048, norms 2 x 64
    assert cost.attention_params(d) == 10_485_888
    assert cost.expert_params(d) * 2 == 18_874_368      # bytes an expert hit
    assert cost.router_params(d) == 131_136
    assert cost.outside_experts_params(d) == (
        8 * 16_783_360 + 2 * 10_485_888 + 2 * 72_351_744 + 8 * 131_136)
    assert cost.pair_flops(d) == 4 * 64 * 32
    assert cost.kv_bytes_per_position(d) == 4_096
    assert cost.tail_bytes_per_slot(d) == 65_536
    head = 2048 * 65536
    outside = cost.outside_experts_params(d)
    c = cost.decode_step_cost(d, 300_000, 256, 8192, 512, 256)
    assert c["bytes"] == (2 * (outside + head + 18_874_368 // 2 * 512
                               + 256 * 2048)
                          + 2 * 256 * 65_536 + 300_000 * 4_096)
    assert c["flops"] == (2.0 * (outside + head) * 256
                          + 2.0 * 9_437_184 * 8192 + 8192.0 * 2 * 300_000)
    m = cost.experts_cost(d, 256, 8192, 512)
    assert m["bytes"] == 2.0 * (8 * 131_136 + 9_437_184 * 512)
    assert m["flops"] == 2.0 * 131_136 * 8 * 256 + 2.0 * 9_437_184 * 8192
    p = cost.paged_cost(d, 1000)
    assert p == {"flops": 8192.0 * 2 * 1000, "bytes": 4_096_000.0}
    assert cost.serve_flops(d, 1000, 500_000, 10, 4000) == (
        2.0 * outside * 1000 + 2.0 * 9_437_184 * 4000
        + 8192.0 * 2 * 500_000 + 2.0 * head * 10)


def test_the_reader_returns_nothing_where_the_program_wrote_nothing(
        monkeypatch):
    """A parent without the scopes and counters: every metric is left out;
    with `pairs_held` on the spans, the share of the peak is the hand
    count."""
    import conv_experts as reader
    import conv_experts_cost as cost
    import program_trace

    span = lambda name, **c: types.SimpleNamespace(  # noqa: E731
        name=name, counters=c, parent=None)
    trace = types.SimpleNamespace(
        path="x", spans=[span("engine.decode.dispatch", active=3,
                              live_positions=9)],
        has_paths=lambda: False, named=lambda n: [])
    monkeypatch.setattr(program_trace, "of", lambda ctx: trace)
    d = published_dims()
    ctx = types.SimpleNamespace(
        dims=d, chips=1, window_s=2.0, peaks={"flops_per_s": 1e14},
        counters={"tokens_processed": 1000, "context_sum": 500_000,
                  "sampled": 10})
    for what in ("step_mfu", "decode_roofline", "moe_roofline",
                 "paged_roofline", "conv_ms", "moe_ms"):
        assert reader.read(ctx, what) is None
    trace.spans = [span("engine.decode.dispatch", state_slots=3),
                   span("engine.decode.book", pairs_held=3000),
                   span("engine.prefill.fetch", chunk_pairs_held=1000)]
    want = 100.0 * cost.serve_flops(d, 1000, 500_000, 10, 4000) / 2.0 / 1e14
    assert reader.read(ctx, "step_mfu") == pytest.approx(want)
    monkeypatch.setattr(program_trace, "of", lambda ctx: None)
    assert reader.read(ctx, "step_mfu") is None
