"""Rehearsal of the cell that serves a decoder of state-space mixers beside
grouped-query attention, on the CPU at tiny sizes, and unit tests of its cost
functions.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_state_space_cell.py -q -p no:cacheprovider

`test_rehearsal.py` already runs the cell with only the keys `tiny.py` knows
shrunk (the mixer at its published sizes, a prefill chunk of 16: one short
chunk of the scan). Here the keys only this model has are shrunk too
(`tiny_state_space.py`), the prefill chunk is two chunks of the scan, and the
faults are planted in a checkout that computes in float32, where the sound
program reads under 1e-4 and each fault 0.05 and more.
"""

import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [HERE, BENCH, os.path.join(BENCH, "readers")]

import tiny_state_space  # noqa: E402
from test_rehearsal import CONTRACT_KEYS, cells, drive  # noqa: E402

CELL = tiny_state_space.CELL


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """bf16 against float32 at these widths, with the multipliers enlarged as
    `tiny_state_space.py` says, reads 0.004 to 0.007 where the chip's size
    reads 0.0003 to 0.0005 (its logits carry 1/128): ten times the tiny
    size's reading is the tiny size's limit, as `tiny.write_tiny_limits`
    has it for the dense cells."""
    dest = tiny_state_space.make_tiny_checkout(
        str(tmp_path_factory.mktemp("mixer")))
    tiny_state_space.write_tiny_limits(dest, 0.05)
    return dest


@pytest.fixture(scope="module")
def exact(tmp_path_factory):
    dest = tiny_state_space.make_tiny_checkout(
        str(tmp_path_factory.mktemp("exact")))
    tiny_state_space.make_exact(dest)
    tiny_state_space.write_tiny_limits(dest, 0.01)
    return dest


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_runs_with_two_chunks_of_the_scan_a_prefill_chunk(checkout,
                                                                   trace):
    last, proc = drive(checkout, CELL, 2 ** 31 + 37 + trace, 2, trace)
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
        assert "step_mfu.serve_falconh1" in want
    assert set(last["metrics"]) == want
    assert all(m["value"] > 0 for m in last["metrics"].values())


@pytest.mark.parametrize("fault", ["state_not_carried", "tail_dropped",
                                   "group_misread", "token_altered"])
def test_a_planted_fault_comes_out_not_correct(exact, fault):
    """A second chunk that starts from a zero state; one that starts without
    the convolution's carried inputs; query heads that read key/value head
    `i % 2`; a token altered where it is produced."""
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


def tiny_dims():
    from references import state_space as ref

    with open(os.path.join(BENCH, "configs", tiny_state_space.CONFIG)) as f:
        cfg = json.load(f)
    cfg.update(tiny_state_space.TINY_MIXER, vocab_size=512)
    return ref.Dims.from_config(cfg)


def test_the_fp8_control_reads_wider_gaps_than_the_reference():
    import jax.numpy as jnp
    import numpy as np
    from references import state_space as ref

    dims = tiny_dims()
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
    from references import state_space as ref

    with open(os.path.join(BENCH, "configs", tiny_state_space.CONFIG)) as f:
        return ref.Dims.from_config(json.load(f))


def test_the_costs_against_hand_counts():
    import state_space_cost as cost

    d = published_dims()
    # W_in 5120 x 9248 and W_out 4096 x 5120 (the issue's 47.35 M and
    # 20.97 M), the convolution's 5 x 5120, dt_bias, A and D of 32, the
    # gated norm's 4096
    assert cost.mixer_params(d) == (47_349_760 + 20_971_520 + 25_600 + 96
                                    + 4096)
    # W_qkv 5120 x 3584 and W_o 2560 x 5120: the issue's 31.46 M
    assert cost.attention_params(d) == 18_350_080 + 13_107_200
    assert cost.layer_params(d) == (68_351_072 + 31_457_280
                                    + 3 * 5120 * 21504)
    assert cost.pair_flops(d) == 4 * 128 * 20
    assert cost.scan_flops_per_token(d) == 4 * 32 * 128 * 256 == 4_194_304
    assert cost.chunk_scan_flops(d) == (2 * 128 * 128 * (512 + 4096)
                                        + 4 * 128 * 4096 * 256)
    assert cost.kv_bytes_per_position(d) == 12_288
    # 32 x 128 x 256 float32 and 3 x 5120 bf16 a layer: 25.2 MB over six
    assert cost.state_bytes_per_slot(d) == 6 * (4_194_304 + 30_720) \
        == 25_350_144
    layers, head = 6 * cost.layer_params(d), 5120 * 261120
    # a decode step of 96 slots over 40,000 live positions
    c = cost.decode_step_cost(d, 40_000, 96, 96)
    assert c["bytes"] == (2 * (layers + head + 96 * 5120)
                          + 2 * 96 * 25_350_144 + 40_000 * 12_288)
    assert c["flops"] == (2.0 * (layers + head) * 96
                          + 4_194_304.0 * 6 * 96 + 10_240.0 * 6 * 40_000)
    m = cost.mixer_step_cost(d, 96)
    assert m["bytes"] == 2.0 * 6 * 68_351_072 + 2 * 96 * 25_350_144
    assert m["flops"] == (2.0 * 68_351_072 + 4_194_304) * 6 * 96
    k = cost.mixer_chunk_cost(d, 200, 2)
    assert k["bytes"] == 2.0 * 6 * 68_351_072 + 2 * 25_350_144
    assert k["flops"] == (2.0 * 68_351_072 * 200
                          + 2.0 * cost.chunk_scan_flops(d)) * 6
    # 1000 tokens over a context sum of 5e5, 10 sampled, 900 through the scan
    assert cost.serve_flops(d, 1000, 500_000, 10, 900) == (
        2.0 * layers * 1000 + 10_240.0 * 6 * 500_000
        + 4_194_304.0 * 6 * 900 + 2.0 * head * 10)


def test_the_reader_returns_nothing_where_the_program_wrote_nothing(
        monkeypatch):
    """A parent without the scopes and counters: every metric is left out;
    with `state_slots` on the dispatch spans, the share of the peak is the
    hand count."""
    import program_trace
    import state_space as reader
    import state_space_cost as cost

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
    for what in ("step_mfu", "decode_roofline", "ssm_ms", "ssm_roofline",
                 "ssm_scan_roofline"):
        assert reader.read(ctx, what) is None
    trace.spans = [span("engine.decode.dispatch", state_slots=300),
                   span("engine.decode.dispatch", state_slots=400),
                   span("engine.prefill.dispatch", state_slots=1,
                        n_valid=200, scan_chunks=2),
                   span("engine.prefill.dispatch", n_valid=999)]
    want = 100.0 * cost.serve_flops(d, 1000, 500_000, 10, 900) / 2.0 / 1e14
    assert reader.read(ctx, "step_mfu") == pytest.approx(want)
    monkeypatch.setattr(program_trace, "of", lambda ctx: None)
    assert reader.read(ctx, "step_mfu") is None
