"""Rehearsal tests of the benchmark's harness, on the CPU at tiny sizes.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q -p no:cacheprovider

Every run of a cell is a process of its own (`drive.py`, which skips only the
look for a chip), from a temporary copy of `BENCHMARK.json` and
`benchmarks/` whose configuration and traffic files are shrunk. Nothing here
is a speed: the numbers these runs print are never written anywhere.
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path[:0] = [HERE, BENCH, os.path.join(BENCH, "readers")]

import tiny  # noqa: E402

ENV = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    dest = str(tmp_path_factory.mktemp("checkout"))
    return tiny.make_tiny_checkout(dest)


def drive(checkout, workload, seed, seconds, trace, fault=""):
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "drive.py"), checkout, workload,
         str(seed), str(seconds), str(trace), fault],
        env=ENV, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    return last, proc


def cells():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _traffic(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("workload", [w["name"] for w in cells()["workloads"]])
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_and_its_last_line_has_the_contracts_keys(
        checkout, workload, trace):
    last, proc = drive(checkout, workload, 2 ** 31 + 11 + trace, 2, trace)
    assert CONTRACT_KEYS <= set(last) and list(last)[-1] == "checks"
    assert last["correct"] is True, proc.stderr[-2000:]
    assert last["attempted"] > 0 and last["failed"] == 0
    bench = cells()
    mine = lambda m: workload in m.get("workloads", [workload])  # noqa: E731
    want = {m["name"] for m in bench["end_to_end" if not trace
                                     else "per_layer"] if mine(m)}
    got = set(last["metrics"])
    if trace:
        # a CPU has no device plane: what is read from the trace is absent,
        # never 0, and everything else is there
        from_trace = {m["name"] for m in bench["per_layer"]
                      if m["source"] == "device_trace"}
        assert got == want - from_trace
        assert {"busy_s", "window_s"} <= set(last["device"])
    else:
        assert got == want
    for m in last["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    # each number compared stands beside its limit, on stderr too
    for name, c in last["checks"].items():
        assert f"check {name}:" in proc.stderr and "limit" in c


def test_without_a_tpu_the_command_exits_nonzero_and_prints_no_result():
    cmd = cells()["command"] + ["--workload", cells()["workloads"][0]["name"],
                               "--seed", "1", "--seconds", "1", "--trace", "0"]
    proc = subprocess.run(cmd, cwd=REPO, env=ENV, capture_output=True,
                          text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr


def test_every_seed_offers_the_same_requests_and_gaps_in_an_order_of_its_own():
    import traffic_gen

    traffic = _traffic("chat")
    a = traffic_gen.offered(traffic, 3, 51.0, 1000)
    b = traffic_gen.offered(traffic, 2 ** 31 + 9, 51.0, 1000)
    shape = lambda reqs: sorted((len(r.prompt), r.max_new) for r in reqs)  # noqa: E731
    gaps = lambda reqs: sorted(round(y.due - x.due, 9)  # noqa: E731
                               for x, y in zip(reqs, reqs[1:]))
    assert shape(a) == shape(b) and gaps(a) == gaps(b)
    # the order is the seed's, over the whole window: the longest answer
    # and the longest gap stand in other places
    longest = lambda reqs: max(range(len(reqs)),  # noqa: E731
                               key=lambda i: reqs[i].max_new)
    widest = lambda reqs: max(range(1, len(reqs)),  # noqa: E731
                              key=lambda i: reqs[i].due - reqs[i - 1].due)
    places = {(longest(traffic_gen.offered(traffic, s, 51.0, 1000)),
               widest(traffic_gen.offered(traffic, s, 51.0, 1000)))
              for s in range(12)}
    assert len({p for p, _ in places}) >= 6 and len({g for _, g in places}) >= 6
    assert a == traffic_gen.offered(traffic, 3, 51.0, 1000)
    assert max(r.due for r in a) < 51.0 and a[0].due == 0.0
    lens = [len(r.prompt) for r in a]
    spec = traffic["prompt_len"]
    assert min(lens) >= spec["min"] and max(lens) <= spec["max"]
    # an even count has no middle request: the two beside it straddle it
    mid = sorted(lens)[(len(lens) - 1) // 2: len(lens) // 2 + 1]
    assert mid[0] <= spec["median"] <= mid[-1] <= 1.01 * spec["median"]
    assert all(len(r.prompt) + r.max_new <= traffic["block_len"]
               * traffic["max_blocks_per_seq"] for r in a)


def test_a_backlog_is_one_round_of_lengths_again_and_again():
    import traffic_gen

    traffic = _traffic("chat_backlog")
    r = traffic["lengths_round"]
    a = traffic_gen.offered(traffic, 3, 51.0, 1000)
    b = traffic_gen.offered(traffic, 2 ** 31 + 9, 51.0, 1000)
    assert len(a) == traffic["backlog_requests"] and all(o.due == 0 for o in a)
    shape = lambda reqs: sorted((len(o.prompt), o.max_new) for o in reqs)  # noqa: E731
    # the head of every seed's queue holds the same lengths, in its own order
    assert shape(a[:r]) == shape(b[:r]) == shape(a[r:2 * r])
    assert [o.max_new for o in a[:r]] != [o.max_new for o in b[:r]]
    assert [o.max_new for o in a[:r]] != [o.max_new for o in a[r:2 * r]]


def test_the_chat_mix_offers_a_hundred_requests_all_due_inside_the_window():
    """A 95th percentile wants some hundreds of requests in the window: at
    0.85/s the cell offered 43 and its percentile jumped with the seed's
    order (PERF.md, PR 35)."""
    import traffic_gen

    seconds = float(cells()["run_seconds"])
    reqs = traffic_gen.offered(_traffic("chat"), 2 ** 31 + 5, seconds, 1000)
    assert len(reqs) >= 100
    assert reqs[0].due == 0.0 and reqs[-1].due < seconds
    assert reqs[-1].due == max(r.due for r in reqs)


def test_the_backlog_is_whole_rounds_and_three_windows_deep():
    """The engine finishes about 237 requests of this mix in a window (PR
    35): a queue three windows deep still stands at the close when a later
    PR has made the engine twice as fast."""
    traffic = _traffic("chat_backlog")
    n, r = traffic["backlog_requests"], traffic["lengths_round"]
    assert n % r == 0 and n >= 3 * 237


@pytest.mark.parametrize("cell", [w for w in cells()["workloads"]
                                  if w["name"].startswith("serve_")],
                         ids=lambda w: w["name"])
def test_a_serving_cells_why_names_the_load_its_traffic_file_holds(cell):
    """`why` is prose and the traffic file is what runs: the rate, or the
    depth of the backlog, stands in both, as the same number."""
    import re

    traffic = _traffic(cell["traffic"])
    if traffic.get("arrivals") == "backlog":
        said = rf"(?<![\d.]){traffic['backlog_requests']}(?![\d.]*\d)"
    else:
        said = rf"(?<![\d.]){re.escape(str(traffic['rate_rps']))}/s"
    assert re.search(said, cell["why"]), (said, cell["why"])


def test_gap_shape_under_one_bursts_and_keeps_the_rate():
    import statistics
    import traffic_gen

    steady = traffic_gen.gap_quantiles(2.0, 400)
    bursty = traffic_gen.gap_quantiles(2.0, 400, shape=0.5)
    assert sum(steady) == pytest.approx(200.0) == pytest.approx(sum(bursty))
    cv = lambda g: statistics.pstdev(g) / statistics.mean(g)  # noqa: E731
    assert cv(steady) == pytest.approx(1.0, abs=0.05)   # exponential
    assert cv(bursty) > 1.8                            # Weibull 0.5: 2.24


def test_the_backlog_cell_ends_at_the_close_with_its_queue(checkout):
    last, proc = drive(checkout, "serve_dsllm7b_backlog", 77, 2, 0)
    assert last["correct"] is True, proc.stderr[-2000:]
    note = proc.stdout.splitlines()[0]
    assert "of=2000" in note and 0 < last["attempted"] < 2000
    assert last["checks"]["queue_empty_before_close"]["value"] == 0
    assert "requests_unfinished" not in last["checks"]
    assert "drained_s=0.0" in note or "drained_s=0.1" in note


def test_a_traffic_file_never_seen_runs_with_no_edit_to_any_file(
        tmp_path):
    """A bursty mix, tiny: one new traffic file, one new limits file and a
    `BENCHMARK.json` entry; no file that exists is edited."""
    dest = tiny.make_tiny_checkout(str(tmp_path))
    bdir = os.path.join(dest, "benchmarks")
    before = {}
    for root, _, files in os.walk(bdir):
        for f in files:
            p = os.path.join(root, f)
            before[p] = open(p, "rb").read()
    with open(os.path.join(bdir, "traffic", "chat.json")) as f:
        bursty = json.load(f)
    bursty.update(gap_shape=0.5, rate_rps=10.0)
    with open(os.path.join(bdir, "traffic", "bursty.json"), "w") as f:
        json.dump(bursty, f)
    with open(os.path.join(bdir, "limits", "serve_tiny_bursty.json"),
              "w") as f:
        json.dump({"served_logit_gap": 0.05}, f)
    with open(os.path.join(dest, "BENCHMARK.json")) as f:
        bench = json.load(f)
    chat = next(w for w in bench["workloads"]
                if w["name"] == "serve_dsllm7b_chat")
    bench["workloads"].append({**chat, "name": "serve_tiny_bursty",
                               "traffic": "bursty"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "serve_dsllm7b_chat" in m.get("workloads", []):
            m["workloads"].append("serve_tiny_bursty")
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    last, proc = drive(dest, "serve_tiny_bursty", 5, 2, 0)
    assert last["correct"] is True and last["attempted"] == 20
    assert set(last["metrics"]) == {"itl_p95_ms", "setup_s"}
    # the first token and the generator's lateness are printed on every
    # run, and are no metrics of the benchmark (PERF.md, section 2)
    assert "note ttft_ms p50=" in proc.stdout and "late_ms p95=" in proc.stdout
    for p, data in before.items():
        assert open(p, "rb").read() == data, p


@pytest.mark.parametrize("workload,fault,failing", [
    ("train_dscoder1b_seq4k", "state_unchanged", "param_change_gap"),
    ("train_dscoder1b_seq4k", "half_batch", "grad_norm_gap"),
    ("serve_dsllm7b_chat", "token_altered", "served_logit_gap"),
    ("serve_dsllm7b_backlog", "token_altered", "served_logit_gap"),
])
def test_a_broken_timed_path_comes_out_not_correct(
        tmp_path, workload, fault, failing):
    """The rest of a run, with the timed path broken underneath: a step that
    hands back its state unchanged; half of the batch left out, the mean
    taken over the rest; a token altered where it is produced. Limits as the
    tiny size needs them (the chip's are set from the chip's readings)."""
    dest = tiny.make_tiny_checkout(str(tmp_path))
    tiny.write_tiny_limits(dest)
    sound, _ = drive(dest, workload, 21, 1, 0)
    assert sound["correct"] is True, sound["checks"]
    broken, _ = drive(dest, workload, 21, 1, 0, fault)
    assert broken["correct"] is False
    assert broken["checks"][failing]["ok"] is False, broken["checks"]


def test_the_control_in_the_programs_place_fails_training(tmp_path):
    """The reference with every product's operands in fp8, in the program's
    place: at least one number reads three times what the program's reads,
    and over the tiny size's limit."""
    import numpy as np
    import harness
    import reference as ref
    import train_cell

    dest = tiny.make_tiny_checkout(str(tmp_path))
    tiny.write_tiny_limits(dest)
    with open(os.path.join(dest, "benchmarks", "configs",
                           "deepseek-coder-1.3b.json")) as f:
        dims = ref.Dims.from_config(json.load(f))
    with open(os.path.join(dest, "benchmarks", "limits",
                           "train_dscoder1b_seq4k.json")) as f:
        limits = json.load(f)
    rng = np.random.default_rng(5)
    batches = [rng.integers(0, dims.vocab, (4, 64)).astype(np.int32)
               for _ in range(3)]
    want = ref.train_three(9, dims, batches, 8e-4)
    got = ref.train_three(9, dims, batches, 8e-4, ref.CONTROL)
    checks, _ = train_cell.compare_training(got, want, limits)
    assert any(not c.ok for c in checks), harness.checks_dict(checks)


def test_the_control_reads_wider_gaps_than_the_reference_when_serving():
    """At each position of the same tokens, the token the fp8 forward pass
    puts first lies below the reference's best somewhere; the reference's
    own first choice never does."""
    import jax.numpy as jnp
    import numpy as np
    import reference as ref

    dims = ref.Dims(vocab=512, d=64, heads=2, ffn=176, layers=2, eps=1e-6,
                    theta=1e4)
    weights = ref.make_weights(3, dims, "bfloat16")
    toks = jnp.asarray(np.random.default_rng(1).integers(0, 512, 96),
                       jnp.int32)
    gap = ref.make_gap_below_best(dims)
    own = ref.make_first_choice(dims, ref.REFERENCE)(weights, toks)
    low = ref.make_first_choice(dims, ref.CONTROL)(weights, toks)
    assert float(gap(weights, toks, own).max()) == 0.0
    assert float(gap(weights, toks, low).max()) > 0.0
