"""Rehearsal of the cell that serves a latent-attention decoder with routed
experts, on the CPU at tiny sizes, and unit tests of its cost functions.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_latent_experts_cell.py -q -p no:cacheprovider

`test_rehearsal.py` already runs the cell with only the keys `tiny.py` knows
shrunk (its prefill chunk of 16 folds). Here the keys only this model has are
shrunk too (`tiny_latent.py`), the chunk is long enough to expand, and the
faults are planted in a checkout that computes in float32, where the sound
program reads 0 and one expert's output left out reads 0.08 and more.
"""

import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [HERE, BENCH, os.path.join(BENCH, "readers")]

import tiny_latent  # noqa: E402
from test_rehearsal import CONTRACT_KEYS, cells, drive  # noqa: E402

CELL = tiny_latent.CELL


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return tiny_latent.make_tiny_checkout(
        str(tmp_path_factory.mktemp("latent")))


@pytest.fixture(scope="module")
def exact(tmp_path_factory):
    dest = tiny_latent.make_tiny_checkout(
        str(tmp_path_factory.mktemp("exact")))
    tiny_latent.make_exact(dest)
    tiny_latent.write_tiny_limits(dest, 0.01)
    return dest


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_runs_with_a_chunk_that_expands(checkout, trace):
    last, proc = drive(checkout, CELL, 2 ** 31 + 29 + trace, 2, trace)
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
        assert "step_mfu.serve_axk1" in want
    assert set(last["metrics"]) == want
    assert all(m["value"] > 0 for m in last["metrics"].values())


@pytest.mark.parametrize("fault", ["expert_left_out", "row_before_rotation",
                                   "token_altered"])
def test_a_planted_fault_comes_out_not_correct(exact, fault):
    """One held expert's output dropped from the sum; the cache's row
    written before the rotation; a token altered where it is produced."""
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
    from references import latent_experts as ref

    with open(os.path.join(BENCH, "configs", tiny_latent.CONFIG)) as f:
        cfg = json.load(f)
    cfg.update(tiny_latent.TINY_LATENT, hidden_size=64, intermediate_size=176,
               vocab_size=512)
    cfg["published"] = dict(cfg["published"], n_routed_experts=24)
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
    from references import latent_experts as ref

    with open(os.path.join(BENCH, "configs", tiny_latent.CONFIG)) as f:
        return ref.Dims.from_config(json.load(f))


def test_the_costs_against_hand_counts():
    import latent_experts_cost as cost

    d = published_dims()
    # W_qa 7168x1536, W_qb 1536x(64x192), W_kva 7168x576, W_kvb
    # 512x(64x256), W_o 8192x7168: the issue's 101.12 M
    assert cost.attention_params(d) == (11_010_048 + 18_874_368 + 4_128_768
                                        + 8_388_608 + 58_720_256)
    assert cost.expert_params(d) == 3 * 7168 * 2048 == 44_040_192
    # 7 attentions, the dense layer's 3 x 7168 x 18432, and in each of the
    # 6 expert layers the router's 7168 x 192 and the shared expert
    assert cost.outside_experts_params(d) == (
        7 * 101_122_048 + 396_361_728 + 6 * (1_376_256 + 44_040_192))
    assert cost.row_dim(d) == 576
    assert cost.pair_flops_expanded(d) == 2 * 64 * 320 == 40_960
    assert cost.pair_flops_folded(d) == 2 * 64 * 1088 == 139_264
    # a decode step of 32 slots over 130,000 live positions, 16 pairs on 9
    # of the 72 held experts
    c = cost.decode_step_cost(d, 130_000, 32, 16, 9)
    weights = 1_376_714_752 + 7168 * 20480 + 9 * 44_040_192 + 32 * 7168
    assert c["bytes"] == 2 * weights + 130_000 * 576 * 2 * 7
    assert c["flops"] == (2.0 * (1_376_714_752 + 7168 * 20480) * 32
                          + 2.0 * 44_040_192 * 16
                          + 139_264.0 * 7 * 130_000)
    a = cost.latent_attention_cost(d, 1000)
    assert a == {"flops": 139_264.0 * 7 * 1000, "bytes": 1000.0 * 576 * 2 * 7}
    e = cost.expert_layers_cost(d, 32, 16, 9)
    assert e["bytes"] == 2.0 * (6 * (1_376_256 + 44_040_192)
                                + 9 * 44_040_192)
    assert e["flops"] == (2.0 * (1_376_256 + 44_040_192) * 6 * 32
                          + 2.0 * 44_040_192 * 16)
    # 1000 tokens over a context sum of 5e6, 10 sampled, 500 pairs held
    assert cost.serve_flops(d, 1000, 5_000_000, 10, 500) == (
        2.0 * 1_376_714_752 * 1000 + 2.0 * 44_040_192 * 500
        + 40_960.0 * 7 * 5_000_000 + 2.0 * 7168 * 20480 * 10)


def test_the_reader_returns_nothing_where_the_program_wrote_nothing(
        monkeypatch):
    """A parent without the scopes and counters: every metric is left out;
    with `pairs_held` on the spans, the share of the peak is the hand count."""
    import latent_experts as reader
    import program_trace

    span = lambda name, **c: types.SimpleNamespace(  # noqa: E731
        name=name, counters=c, parent=None)
    trace = types.SimpleNamespace(
        path="x", spans=[span("engine.decode.book", emitted=3)],
        has_paths=lambda: False, named=lambda n: [])
    monkeypatch.setattr(program_trace, "of", lambda ctx: trace)
    d = published_dims()
    ctx = types.SimpleNamespace(
        dims=d, chips=1, window_s=2.0, peaks={"flops_per_s": 1e14},
        counters={"tokens_processed": 1000, "context_sum": 5_000_000,
                  "sampled": 10})
    for what in ("step_mfu", "decode_roofline", "latent_ms", "moe_ms",
                 "latent_roofline", "moe_roofline"):
        assert reader.read(ctx, what) is None
    trace.spans = [span("engine.decode.book", pairs_held=300,
                        chunk_pairs_held=150),
                   span("engine.prefill.fetch", chunk_pairs_held=50),
                   span("engine.decode.dispatch", pairs_held=999)]
    import latent_experts_cost as cost
    want = 100.0 * cost.serve_flops(d, 1000, 5_000_000, 10, 500) / 2.0 / 1e14
    assert reader.read(ctx, "step_mfu") == pytest.approx(want)
    monkeypatch.setattr(program_trace, "of", lambda ctx: None)
    assert reader.read(ctx, "step_mfu") is None
