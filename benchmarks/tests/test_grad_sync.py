"""The gradient exchange's readers (`readers/grad_sync.py`) on synthetic
events with exact values, and the four-chip training cell with the exchange
between chips left out, on four CPU host devices.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_grad_sync.py -q -p no:cacheprovider
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

import grad_sync  # noqa: E402
import program_trace  # noqa: E402
import tiny  # noqa: E402

SYNC = "jit(local_step)/shard_map/grad_sync/psum"
FWD = "jit(local_step)/shard_map/jvp(mlp)/dot_general"
OPT = "jit(local_step)/shard_map/optimizer/add"
MS = 1_000_000      # the synthetic times are in ms, the trace's in ns


def op(name, start, end, path):
    return (f"%{name} = f32[8]{{0}} {name.split('.')[0]}(%p)", start * MS,
            end * MS, path)


class FakeTrace:
    path = "synthetic.xplane.pb"

    def __init__(self, runs):
        self.runs = runs          # chip -> [(start, end)]

    def program_intervals(self, name="", chip=0):
        return [(s * MS, e * MS) for s, e in self.runs.get(chip, [])]


class FakeProgram:
    def __init__(self, spans):
        self.spans = spans

    def named(self, name):
        return [s for s in self.spans if s.name == name]


class Ctx:
    def __init__(self, runs, steps):
        self.trace = FakeTrace(runs)
        self.counters = {"steps": steps}


def dispatch(it, start, wire=3000):
    return program_trace.Span(
        "train.dispatch", start * MS, (start + 5) * MS,
        {"it": it, "sync_payload_bytes": 2 * wire // 3,
         "sync_wire_bytes": wire, "collectives": 2})


def read(monkeypatch, ops, runs, spans, what):
    monkeypatch.setattr(grad_sync, "chip_ops", lambda path: ops)
    monkeypatch.setattr(program_trace, "of",
                        lambda ctx: FakeProgram(spans))
    monkeypatch.setattr(program_trace, "_NOTED", set())
    ctx = Ctx(runs, sum(len(r) for r in runs.values()) // len(runs))
    return grad_sync.read(ctx, what)


FULLY_EXPOSED = [op("fusion.1", 0, 600, FWD), op("psum.1", 600, 900, SYNC),
                 op("fusion.2", 900, 1000, OPT)]
HALF_HIDDEN = [op("fusion.1", 0, 700, FWD), op("psum.1", 500, 900, SYNC),
               op("fusion.2", 900, 1000, OPT)]
ASYNC_PAIR = [
    op("fusion.1", 0, 300, FWD),
    ("%all-reduce-start.3 = f32[8]{0} all-reduce-start(%p), replica_groups="
     "{{0,1,2,3}}", 300 * MS, 310 * MS, SYNC),
    op("fusion.4", 310, 700, FWD),
    ("%all-reduce-done.3 = f32[8]{0} all-reduce-done(%all-reduce-start.3)",
     700 * MS, 800 * MS, SYNC),
    op("fusion.2", 800, 1000, OPT)]


# the compiler may leave a done without a scope path (as `copy-done`)
DONE_UNSCOPED = [x if "-done" not in x[0] else x[:3] + ("",)
                 for x in ASYNC_PAIR]


@pytest.mark.parametrize("ops,exposed,flight", [
    (FULLY_EXPOSED, 30.0, 300),
    (HALF_HIDDEN, 20.0, 400),
    (ASYNC_PAIR, 11.0, 500),     # the start's own 10 ms, the done's 100
    (DONE_UNSCOPED, 11.0, 500),
], ids=["fully_exposed", "half_hidden", "async_pair", "done_unscoped"])
def test_the_exposed_share_and_the_rate_on_synthetic_runs(
        monkeypatch, ops, exposed, flight):
    """One run of 1000 ms on each of four chips, chip 0 as given and the
    others wholly exposed (their shares are in the note only); one dispatch
    of 3000 wire bytes: bytes over the in-flight seconds."""
    by_chip = {0: ops, **{c: FULLY_EXPOSED for c in (1, 2, 3)}}
    runs = {c: [(0, 1000)] for c in range(4)}
    spans = [dispatch(9, -100)]
    assert read(monkeypatch, by_chip, runs, spans, "exposed_share") \
        == pytest.approx(exposed)
    assert read(monkeypatch, by_chip, runs, spans, "gbps") \
        == pytest.approx(3000 / (flight / 1e3) / 1e9)


def test_a_run_whose_dispatch_lies_outside_the_trace_pairs_with_none(
        monkeypatch):
    """Three whole runs, the first dispatched before the trace began: the
    two spans of the trace pair with the second and third runs, whose
    exchanges take 300 and 150 ms."""
    shifted = [(n, s + 2000 * MS, e + 2000 * MS, p)
               for n, s, e, p in FULLY_EXPOSED]
    late = [op("fusion.1", 4000, 4600, FWD), op("psum.1", 4600, 4750, SYNC),
            op("fusion.2", 4750, 5000, OPT)]
    ops = {0: FULLY_EXPOSED + shifted + late}
    runs = {0: [(0, 1000), (2000, 3000), (4000, 5000)]}
    spans = [dispatch(5, 1500, wire=3000), dispatch(6, 3500, wire=6000)]
    assert read(monkeypatch, ops, runs, spans, "gbps") == pytest.approx(
        (3000 + 6000) / ((300 + 150) / 1e3) / 1e9)
    assert read(monkeypatch, ops, runs, spans, "exposed_share") \
        == pytest.approx(100.0 * (300 + 300 + 150) / 3000)


def test_nothing_to_read_is_none_and_never_zero(monkeypatch):
    """No `grad_sync` operation (a one-chip step whose `pmean` compiled to
    nothing), or no counters on the dispatch spans (a commit before them)."""
    runs = {0: [(0, 1000)]}
    plain = {0: [op("fusion.1", 0, 1000, FWD)]}
    assert read(monkeypatch, plain, runs, [dispatch(0, -10)],
                "exposed_share") is None
    bare = program_trace.Span("train.dispatch", -10 * MS, 0, {"it": 0})
    assert read(monkeypatch, {0: FULLY_EXPOSED}, runs, [bare], "gbps") is None
    assert read(monkeypatch, {0: FULLY_EXPOSED}, runs, [bare],
                "exposed_share") == pytest.approx(30.0)


def test_a_four_chip_trace_read_from_its_bytes(tmp_path, capsys):
    """The same numbers through the readers' own parsing: an `.xplane.pb`
    written by hand, four device planes of two runs each (chip 2's
    exchange half hidden) and the host's two dispatch spans."""
    import harness
    import xplane
    from test_program_trace import plane, stat

    base = 10 ** 12                                   # ns
    ops_md = {5: ("%fusion.1 = f32[8]{0} fusion(%p)", [stat(21, str=FWD)]),
              6: ("%all-reduce.2 = f32[8]{0} all-reduce(%g)",
                  [stat(21, str=SYNC)]),
              7: ("%fusion.3 = f32[8]{0} fusion(%q)", [stat(21, str=OPT)]),
              8: ("jit_local_step(3)", [])}

    def device(chip, compute_ms):
        events = []
        for r in (0, 2000):
            events += [(5, (r) * MS * 1000, compute_ms * MS * 1000, []),
                       (6, (r + 600) * MS * 1000, 300 * MS * 1000, []),
                       (7, (r + 900) * MS * 1000, 100 * MS * 1000, [])]
        return plane(f"/device:TPU:{chip}", [
            ("XLA Modules", base, [(8, r * MS * 1000, 1000 * MS * 1000, [])
                                   for r in (0, 2000)]),
            ("XLA Ops", base, events)], ops_md, {21: "tf_op"})

    def span(mid, at_ms):
        return (mid, at_ms * MS * 1000, 5 * MS * 1000,
                [stat(31, int64=mid), stat(32, int64=2 * 3000 // 3),
                 stat(33, int64=3000), stat(34, int64=2)])

    host = plane("/host:CPU", [("main", base, [span(0, -100), span(1, 1900)])],
                 {0: ("train.dispatch", []), 1: ("train.dispatch", [])},
                 {31: "it", 32: "sync_payload_bytes", 33: "sync_wire_bytes",
                  34: "collectives"})
    f = tmp_path / "four.xplane.pb"
    f.write_bytes(host + b"".join(device(c, 750 if c == 2 else 600)
                                  for c in range(4)))
    program_trace._NOTED.clear()
    ctx = harness.RunContext(
        cell=None, dims=None, peaks={}, chips=4, window=(0.0, 1.0),
        counters={"steps": 2}, spans={}, records=[], steps=[],
        trace=xplane.Trace(str(f)))
    assert grad_sync.read(ctx, "exposed_share", "local_step") \
        == pytest.approx(30.0)
    assert "2=15.0000%" in capsys.readouterr().out
    assert grad_sync.read(ctx, "gbps", "local_step") == pytest.approx(
        2 * 3000 / 0.6 / 1e9)


# ------------------------------------------------------ the planted fault

FOUR = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO,
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
# The exchange between chips left out: the gradient's `pmean` hands back
# each chip's own gradient, in the run's own process.
NO_EXCHANGE = """
import sys
from ddl25spring_tpu.telemetry import comm
pmean = comm.pmean
def local_only(x, axis_name, *, label=None, scale=1):
    return x if label == "grad_allreduce" else pmean(
        x, axis_name, label=label, scale=scale)
comm.pmean = local_only
sys.path.insert(0, sys.argv[1])
import drive
drive.main(*sys.argv[2:])
"""


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    dest = tiny.make_tiny_checkout(str(tmp_path_factory.mktemp("dp4")))
    with open(os.path.join(dest, "benchmarks", "limits",
                           "train_dscoder1b_dp4.json"), "w") as f:
        json.dump({"grad_norm_gap": 3e-3, "param_change_gap": 5e-3}, f)
    return dest


def drive(checkout, fault):
    code = NO_EXCHANGE if fault else (
        "import sys; sys.path.insert(0, sys.argv[1]); import drive; "
        "drive.main(*sys.argv[2:])")
    proc = subprocess.run(
        [sys.executable, "-c", code, HERE, checkout, "train_dscoder1b_dp4",
         "2147483671", "1", "0"],
        env=FOUR, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("fault,correct", [(False, True), (True, False)],
                         ids=["sound", "exchange_left_out"])
def test_the_exchange_left_out_comes_out_not_correct(checkout, fault,
                                                     correct):
    last = drive(checkout, fault)
    assert last["device"]["count"] == 4
    assert last["correct"] is correct, last["checks"]
    if fault:
        assert last["checks"]["grad_norm_gap"]["ok"] is False


# --------------------------------------------- the limits' readings, split

def limits_tool(checkout, *args):
    proc = subprocess.run(
        [sys.executable, os.path.join(checkout, "benchmarks", "tools",
                                      "train_limits.py"),
         "--workload", "train_dscoder1b_dp4", "--rehearse", *args],
        env=FOUR, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return [json.loads(line) for line in proc.stdout.splitlines()
            if line.startswith("{")]


def test_the_limits_tool_reads_program_control_and_fault_apart(
        checkout, tmp_path):
    """The rows cut as the data path cuts them, the references over them on
    one device, the program on four fed those rows, and the comparison:
    the sound program is `correct`, the exchange left out and chip 0's rows
    alone are not, and they read alike."""
    rows, prog, fault = (str(tmp_path / d) for d in ("rows", "p", "f"))
    seeds = "7,2147483999"
    limits_tool(checkout, "--mode", "rows", "--seeds", seeds, "--out", rows)
    ref = {r["seed"]: r for r in limits_tool(
        checkout, "--mode", "reference", "--out", rows, "--control", "7",
        "--alone", "7")}
    assert sorted(ref) == [7, 2147483999]
    assert ref[7]["chip0_rows_alone"]["correct"] is False
    assert "control" not in ref[2147483999]
    limits_tool(checkout, "--mode", "program", "--seeds", seeds, "--out",
                prog, "--rows", rows)
    limits_tool(checkout, "--mode", "program", "--seeds", "7", "--out",
                fault, "--rows", rows, "--fault", "no_exchange")
    sound = limits_tool(checkout, "--mode", "compare", "--out", prog,
                        "--want", rows)
    assert [r["program"]["correct"] for r in sound] == [True, True]
    [left_out] = limits_tool(checkout, "--mode", "compare", "--out", fault,
                             "--want", rows)
    gap = left_out["no_exchange"]["checks"]["grad_norm_gap"]["value"]
    assert left_out["no_exchange"]["correct"] is False
    assert gap == pytest.approx(
        ref[7]["chip0_rows_alone"]["checks"]["grad_norm_gap"]["value"],
        rel=0.01)
