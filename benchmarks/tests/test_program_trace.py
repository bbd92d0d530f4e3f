"""The readers of what the program itself writes into a trace: the helper
(`readers/program_trace.py`) and each new reader, on a span and scope table
made by hand, on a few bytes of `XSpace` made by hand, and on the two traces
recorded on the chip in PR 27 (`tools/record_program_fixtures.py`).

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q -p no:cacheprovider
"""

import gzip
import json
import os
import re
import shutil
import struct
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path[:0] = [REPO, BENCH, os.path.join(BENCH, "readers")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import harness  # noqa: E402
import program_trace as P  # noqa: E402
import reference as ref  # noqa: E402

MS = 1_000_000                      # ns


# ----------------------------------------------------- a trace made by hand

class HandTrace:
    """What a reader asks of `xplane.Trace`, from intervals given by hand."""

    def __init__(self, path, span, busy, programs):
        self.path, self._span, self._busy = path, span, busy
        self._programs = programs           # name -> [(start, end)]
        self.ops = {0: [("op", s, e) for s, e in busy]}

    def span(self):
        return self._span

    def busy_intervals(self, chip=0):
        return list(self._busy)

    def program_intervals(self, name="", chip=0):
        return [iv for n, ivs in self._programs.items() if name in n
                for iv in ivs]


def hand_ctx(name, spans, ops, span, busy, programs=None, **ctx):
    """A context whose program trace is `spans` and `ops` as given."""
    pt = object.__new__(P.ProgramTrace)
    pt.path, pt.size = name, 0
    pt.spans = P.nest([P.Span(*s) for s in spans])
    pt.ops = list(ops)
    pt._leaves = sorted((o for o in ops if not P.CONTAINERS.match(o[0])),
                        key=lambda o: o[1] + o[2])
    pt._middles = [(o[1] + o[2]) // 2 for o in pt._leaves]
    P._CACHE.clear()
    P._CACHE[name] = pt
    trace = HandTrace(name, span, busy, programs or {})
    return types.SimpleNamespace(trace=trace, counters={}, **ctx)


def reader(name):
    return harness._load_reader(name)


TICK = [   # one tick of 100 ms: name, start, end, counters
    ("serve.tick", 0, 100 * MS, {"n": 7}),
    ("serve.admit", 1 * MS, 3 * MS, {"admitted": 0}),
    ("engine.step", 3 * MS, 90 * MS, {}),
    ("engine.decode.stage", 4 * MS, 10 * MS, {}),
    ("engine.decode.dispatch", 10 * MS, 12 * MS,
     {"dispatch": 3, "active": 2, "live_positions": 1000,
      "gathered_positions": 4096}),
    ("engine.decode.fetch", 12 * MS, 80 * MS, {}),
    ("engine.decode.book", 80 * MS, 88 * MS, {"emitted": 2}),
    ("serve.emit", 90 * MS, 98 * MS, {"tokens": 2, "retired": 0}),
]


def test_spans_nest_by_time_and_the_innermost_covers_each_instant():
    spans = P.nest([P.Span(*s) for s in TICK])
    by = {s.name: s for s in spans}
    assert by["serve.tick"].parent is None and by["serve.tick"].depth == 0
    assert by["engine.step"].parent is by["serve.tick"]
    assert by["engine.decode.fetch"].parent is by["engine.step"]
    assert by["engine.decode.fetch"].depth == 2
    assert by["serve.emit"].parent is by["serve.tick"]
    segs = P.innermost_segments(spans)
    assert segs[0] == (0, 1 * MS, "serve.tick")
    assert (3 * MS, 4 * MS, "engine.step") in segs
    assert (12 * MS, 80 * MS, "engine.decode.fetch") in segs
    assert (88 * MS, 90 * MS, "engine.step") in segs
    assert segs[-1] == (98 * MS, 100 * MS, "serve.tick")
    # disjoint, in order, and they cover the tick
    assert all(a[1] == b[0] for a, b in zip(segs, segs[1:]))
    assert sum(e - s for s, e, _ in segs) == 100 * MS


def test_idle_is_split_by_instant_and_the_causes_sum_to_the_idle_share():
    # the chip runs 14..78 ms; the trace's span is 0..120 ms (the benchmark's
    # own 20 ms after the tick are under no span of the program)
    ctx = hand_ctx("by-hand-idle", TICK, [], (0, 120 * MS),
                   [(14 * MS, 78 * MS)])
    split = P.idle_split(ctx)
    # before the run: tick 1, admit 2, step 1, stage 6, dispatch 2, fetch 2
    # after it: fetch 2, book 8, step 2, emit 8, tick 2, none 20
    assert split["stage"] == pytest.approx(100 * 8 / 120)
    assert split["sync"] == pytest.approx(100 * 4 / 120)
    assert split["book"] == pytest.approx(100 * (2 + 8 + 8) / 120)
    assert split["other"] == pytest.approx(100 * (1 + 1 + 2 + 2 + 20) / 120)
    assert split["total"] == pytest.approx(100 * (1 - 64 / 120))
    assert sum(split[k] for k in ("stage", "sync", "book", "other")) == \
        pytest.approx(split["total"])
    # the gap 78..120 ms lies under fetch, book, step, emit, tick and no
    # span: it is not given whole to the book loop that covers most of it
    for cause in ("stage", "sync", "book"):
        assert reader("idle_by_cause").read(ctx, cause) == split[cause]


def test_idle_readers_return_nothing_where_the_program_left_no_span():
    ctx = hand_ctx("by-hand-parent", [], [], (0, 100 * MS),
                   [(10 * MS, 90 * MS)])
    assert P.idle_split(ctx) is None
    assert reader("idle_by_cause").read(ctx, "stage") is None
    assert reader("program_span_share").read(ctx, ["train.data"]) is None
    ctx.trace = None
    assert reader("idle_by_cause").read(ctx, "stage") is None
    assert reader("scope_phase_ms").read(ctx, "backward") is None
    assert reader("decode_part_ms").read(ctx, "decode_step", "paged") is None
    assert reader("paged_attn_roofline").read(ctx, "decode_step") is None


def test_host_share_is_the_union_of_the_named_spans_inside_the_span():
    spans = [("train.data", 0, 10 * MS, {}),
             ("train.dispatch", 8 * MS, 20 * MS, {"it": 4}),   # overlaps
             ("train.sink", 20 * MS, 90 * MS, {}),             # left out
             ("train.checkpoint", 95 * MS, 130 * MS, {})]      # cut at 100
    ctx = hand_ctx("by-hand-loop", spans, [], (5 * MS, 100 * MS), [])
    got = reader("program_span_share").read(
        ctx, ["train.data", "train.dispatch", "train.checkpoint"])
    assert got == pytest.approx(100 * (15 + 5) / 95)


STEP = "jit(local_step)"
BWD = STEP + "/transpose(jvp())/while/body/closed_call/checkpoint"


def train_ops(t0):
    """One step of 100 ms from `t0`, one operation a phase, a `while` round
    some of them, and 2 ms in which no operation runs."""
    rows = [("fusion.1 f32[8]", 0, 10, STEP + "/jvp()/embed/gather:"),
            ("while.2", 10, 90, STEP + "/jvp()/while:"),
            ("fusion.3 bf16[8]", 10, 30, STEP + "/jvp()/while/body/attn/dot:"),
            ("fusion.4 bf16[8]", 30, 38, STEP + "/jvp()/head_loss/dot:"),
            ("fusion.5 bf16[8]", 38, 44,
             STEP + "/transpose(jvp())/head_loss/dot:"),
            ("flash_attention_fwd.1 bf16[8]", 44, 56,
             BWD + "/rematted_computation/attn/pallas_call:"),
            ("flash_attention_dq.1 bf16[8]", 56, 80,
             BWD + "/attn/pallas_call:"),
            ("fusion.6 f32[8]", 82, 100, STEP + "/optimizer/mul:")]
    return [(n, t0 + a * MS, t0 + b * MS, p) for n, a, b, p in rows]


def test_train_phases_by_scope_path_sum_to_the_step():
    # two whole runs, and a third that the trace's edge cut after 3 ops
    ops = train_ops(0) + train_ops(100 * MS) + train_ops(200 * MS)[:3]
    runs = [(0, 100 * MS), (100 * MS, 200 * MS), (200 * MS, 230 * MS)]
    ctx = hand_ctx("by-hand-train", [], ops, (0, 230 * MS), [],
                   {"jit_local_step(1)": runs})
    ctx.counters = {"steps": 3}
    ph = P.step_phases(ctx)
    assert ph["step"] == pytest.approx(100.0)
    assert ph["backward"] == pytest.approx(24.0)
    assert ph["remat"] == pytest.approx(12.0)
    assert ph["head_loss"] == pytest.approx(14.0)      # forward and backward
    assert ph["optimizer"] == pytest.approx(18.0)
    assert ph["forward_ops"] == pytest.approx(30.0)    # the container is out
    assert ph["forward"] == pytest.approx(32.0)        # the rest of the run
    assert sum(ph[k] for k in ("forward", "backward", "remat", "head_loss",
                               "optimizer")) == pytest.approx(ph["step"])
    for phase in ("backward", "remat", "head_loss", "optimizer"):
        assert reader("scope_phase_ms").read(ctx, phase) == ph[phase]
    # each run standing for two steps halves every number
    ctx.counters = {"steps": 6}
    P._NOTED.clear()
    assert P.step_phases(ctx)["backward"] == pytest.approx(12.0)


def test_a_program_without_our_scopes_gives_jaxs_phases_only():
    """The parent of PR 27: `transpose(...)` and `rematted_computation` are
    JAX's, `optimizer` and `head_loss` ours."""
    ops = [(n, s, e, p.replace("/head_loss", "").replace("/optimizer", ""))
           for n, s, e, p in train_ops(0)]
    ctx = hand_ctx("by-hand-unnamed", [], ops, (0, 100 * MS), [],
                   {"jit_local_step(1)": [(0, 100 * MS)]})
    ctx.counters = {"steps": 1}
    assert reader("scope_phase_ms").read(ctx, "backward") == \
        pytest.approx(30.0)
    assert reader("scope_phase_ms").read(ctx, "remat") == pytest.approx(12.0)
    assert reader("scope_phase_ms").read(ctx, "optimizer") is None
    assert reader("scope_phase_ms").read(ctx, "head_loss") is None
    # and one whose operations carry no path at all gives nothing
    bare = [(n, s, e, "") for n, s, e, _ in ops]
    ctx = hand_ctx("by-hand-bare", [], bare, (0, 100 * MS), [],
                   {"jit_local_step(1)": [(0, 100 * MS)]})
    assert reader("scope_phase_ms").read(ctx, "backward") is None


DEC = "jit(decode_step)/layers/while/body"
BLOCK = DEC + "/closed_call"


def decode_ops(t0, attend_ms, hoisted=False):
    """One decode run of 50 ms from `t0`. With `hoisted`, the conversion of
    the gathered cache stands outside `paged.attend`, under the scan alone,
    as the compiler puts it on the chip."""
    convert = (DEC[:-len("/body")] + ":" if hoisted
               else BLOCK + "/paged.attend/convert_element_type:")
    rows = [("fusion.0 bf16[8]", 0, 1, "jit(decode_step)/embed/gather:"),
            ("while.9", 1, 48, DEC[:-len("/body")] + ":"),
            ("fusion.1 bf16[8]", 1, 5, BLOCK + "/qkv/dot_general:"),
            ("fusion.2 bf16[8]", 5, 6, BLOCK + "/paged.write/scatter:"),
            ("fusion.3 bf16[8]", 6, 16, BLOCK + "/paged.gather/gather:"),
            ("convert.4 f32[8]", 16, 20, convert),
            ("fusion.5 f32[8]", 20, 20 + attend_ms,
             BLOCK + "/paged.attend/dot_general:"),
            ("fusion.6 bf16[8]", 41, 44, BLOCK + "/mlp/dot_general:"),
            ("fusion.7 bf16[8]", 44, 46, DEC + "/dynamic_update_slice:"),
            ("copy.8 bf16[8]", 48, 49, "")]
    return [(n, t0 + a * MS, t0 + b * MS, p) for n, a, b, p in rows]


def serve_ctx(name="by-hand-serve", hoisted=False):
    dims = ref.Dims(vocab=512, d=64, heads=2, ffn=176, layers=2, eps=1e-6,
                    theta=1e4)
    # three whole runs; the first was dispatched before the trace began
    ops = (decode_ops(0, 10, hoisted) + decode_ops(100 * MS, 20, hoisted)
           + decode_ops(200 * MS, 14, hoisted))
    runs = [(0, 50 * MS), (100 * MS, 150 * MS), (200 * MS, 250 * MS)]
    # the second run seems to start before its dispatch span does, as it
    # can where host and device clocks agree to a fraction of a ms only
    spans = [("engine.step", 85 * MS, 152 * MS, {}),
             ("engine.decode.dispatch", 100 * MS + 300, 102 * MS,
              {"dispatch": 1, "active": 2, "live_positions": 1000,
               "gathered_positions": 4096}),
             ("engine.step", 185 * MS, 252 * MS, {}),
             ("engine.decode.dispatch", 190 * MS, 192 * MS,
              {"dispatch": 2, "active": 2, "live_positions": 3000,
               "gathered_positions": 4096})]
    cell = types.SimpleNamespace(config={
        "cache_dtype": "bfloat16", "weights_dtype": {"serve": "bfloat16"}})
    return hand_ctx(name, spans, ops, (0, 250 * MS), [],
                    {"jit_decode_step(9)": runs}, dims=dims, cell=cell,
                    peaks={"flops_per_s": 1e12, "hbm_bytes_per_s": 1e9})


def test_a_decode_run_is_its_dense_paged_and_unscoped_parts(capsys):
    P._NOTED.clear()
    ctx = serve_ctx()
    parts = P.decode_parts(ctx, "decode_step")
    # runs: paged 1 + 10 + 4 + (10, 20, 14); unscoped 2 + 1; the container
    # `while.9` is in no sum
    assert parts["paged"] == pytest.approx(15 + 14)
    assert parts["unscoped"] == pytest.approx(3)
    assert parts["run"] == pytest.approx(50)
    assert parts["dense"] == pytest.approx(50 - 29 - 3)
    assert reader("decode_part_ms").read(ctx, "decode_step", "paged") == \
        parts["paged"]
    assert reader("decode_part_ms").read(ctx, "decode_step", "unscoped") == \
        parts["unscoped"]
    # run by run the three are the run, whatever the medians do
    for r0, r1, ops in P.whole_runs(ctx, P.of(ctx), "decode_step")[0]:
        paged, unscoped = P.cache_ns(ops)
        dense_ops = sum(e - s for _, s, e, p in ops
                        if P.decode_part_of(p) == "dense")
        assert paged + unscoped + dense_ops <= r1 - r0
    note = capsys.readouterr().out
    # weights once: (2 x (4 x 64^2 + 3 x 64 x 176) + 64 x 512) x 2 B / 1e9
    assert "weights once over the peak bytes/s is 0.266" in note
    assert "fusion.7 bf16[8] 2.000 [" + DEC + "/dynamic_update_slice:]" \
        in note and "copy.8 bf16[8] 1.000 [no path]" in note


def test_hoisting_an_operation_moves_its_time_and_not_the_roofline():
    """The compiler may lift an operation out of the scope it was written
    in: its time goes from `paged_attn_ms` to `decode_unscoped_ms`, and
    `paged_attn_roofline`, whose time is both, stays where it was."""
    P._NOTED.clear()
    inside = serve_ctx("by-hand-inside")
    a = P.decode_parts(inside, "decode_step")
    roof_a = reader("paged_attn_roofline").read(inside, "decode_step")
    hoisted = serve_ctx("by-hand-hoisted", hoisted=True)
    b = P.decode_parts(hoisted, "decode_step")
    roof_b = reader("paged_attn_roofline").read(hoisted, "decode_step")
    assert b["paged"] == pytest.approx(a["paged"] - 4)
    assert b["unscoped"] == pytest.approx(a["unscoped"] + 4)
    assert b["dense"] == pytest.approx(a["dense"])
    assert roof_b == pytest.approx(roof_a)


def test_paged_roofline_pairs_runs_with_dispatch_spans_and_leaves_the_rest(
        capsys):
    P._NOTED.clear()
    ctx = serve_ctx()
    got = reader("paged_attn_roofline").read(ctx, "decode_step")
    # K and V of a position: 2 x 2 layers x 64 wide x 2 bytes = 512 bytes;
    # bandwidth-bound at these peaks: 4000 positions x 512 B / 1e9 B/s
    least = 4000 * 512 / 1e9
    # paged and unscoped of the two paired runs; the first pairs with none
    took = ((15 + 20 + 3) + (15 + 14 + 3)) / 1e3
    assert got == pytest.approx(100 * least / took)
    note = capsys.readouterr().out
    assert "paired=2 unpaired=1" in note and "live_positions=4000" in note
    assert "paged_s=0.064000 unscoped_s=0.006000" in note
    cost = reader("paged_attn_roofline").attention_cost(ctx.dims, 1000, 2)
    assert cost == {"flops": 4.0 * 64 * 2 * 1000, "bytes": 512.0 * 1000}


def test_a_program_without_the_paged_scopes_gives_no_decode_part(capsys):
    """The parent of PR 27, or an executable that the compile cache handed
    back with the parent's names: paths, but none of the program's scopes."""
    P._NOTED.clear()
    ctx = serve_ctx("by-hand-parent-serve")
    pt = P.of(ctx)
    strip = re.compile(r"/(layers|embed|qkv|paged\.\w+|attn_out|mlp)(?=/)")
    pt.ops = [(n, s, e, strip.sub("", p)) for n, s, e, p in pt.ops]
    pt._leaves = [(n, s, e, strip.sub("", p)) for n, s, e, p in pt._leaves]
    assert P.decode_parts(ctx, "decode_step") is None
    assert "came from a compile cache" in capsys.readouterr().out
    assert reader("decode_part_ms").read(ctx, "decode_step", "unscoped") \
        is None
    assert reader("paged_attn_roofline").read(ctx, "decode_step") is None


# ------------------------------------------- a few bytes of XSpace, by hand

def varint(x):
    out = bytearray()
    x &= (1 << 64) - 1
    while True:
        b = x & 0x7F
        x >>= 7
        out.append(b | (0x80 if x else 0))
        if not x:
            return bytes(out)


def field(num, value):
    if isinstance(value, int):
        return varint(num << 3) + varint(value)
    if isinstance(value, float):
        return varint(num << 3 | 1) + struct.pack("<d", value)
    if isinstance(value, str):
        value = value.encode()
    return varint(num << 3 | 2) + varint(len(value)) + value


def stat(meta_id, **value):
    num = {"uint64": 3, "int64": 4, "str": 5, "ref": 7}
    (kind, v), = value.items()
    return field(1, meta_id) + field(num[kind], v)


def plane(name, lines, event_md, stat_md):
    out = field(2, name)
    for lname, ts, events in lines:
        body = field(2, lname) + field(3, ts)
        for mid, off, dur, stats in events:
            ev = field(1, mid) + field(2, off) + field(3, dur)
            body += field(4, ev + b"".join(field(4, s) for s in stats))
        out += field(3, body)
    for mid, (ename, stats) in event_md.items():
        md = field(1, mid) + field(2, ename) + b"".join(
            field(5, s) for s in stats)
        out += field(4, field(1, mid) + field(2, md))
    for mid, sname in stat_md.items():
        out += field(5, field(1, mid) + field(2, field(1, mid)
                                              + field(2, sname)))
    return field(1, out)


def test_the_wire_reader_finds_spans_counters_and_scope_paths(tmp_path):
    host = plane("/host:CPU", [("main", 1000, [
        (1, 2_000_000, 50_000_500, [stat(11, int64=46), stat(12, uint64=3)]),
        (2, 3_000_000, 1_000_000, [stat(13, str="5")]),
        (3, 9_000_000, 1_000_000, [])])],
        {1: ("serve.tick", []), 2: ("serve.admit", []),
         3: ("bench.tick#46", [])},
        {11: "n", 12: "queued", 13: "admitted"})
    path_a = "jit(decode_step)/while/body/closed_call/paged.gather/gather:"
    device = plane("/device:TPU:0", [
        ("XLA Modules", 0, [(7, 0, 9_000_000, [])]),
        ("XLA Ops", 500, [(5, 1_000_000, 2_000_999, []),
                          (6, 4_000_000, 1_000_000, [])])],
        {5: ("%fusion.153 = bf16[2048,16,32,128]{3,2,1,0} fusion(%p)",
             [stat(21, str=path_a)]),
         6: ("%copy.21 = bf16[16,32]{1,0} copy(%x)", [stat(21, ref=22)]),
         7: ("jit_decode_step(1)", [])},
        {21: "tf_op", 22: "jit(decode_step)/while/body/transpose:"})
    other = plane("/device:TPU:1", [], {}, {})
    f = tmp_path / "hand.xplane.pb"
    f.write_bytes(host + device + other)
    pt = P.ProgramTrace(str(f))
    assert [(s.name, s.start, s.end, s.counters, s.depth)
            for s in pt.spans] == [
        ("serve.tick", 3000, 53000, {"n": 46, "queued": 3}, 0),
        ("serve.admit", 4000, 5000, {"admitted": 5}, 1)]
    assert pt.ops == [
        ("fusion.153 bf16[2048,16,32,128]", 1500, 3500, path_a),
        ("copy.21 bf16[16,32]", 4500, 5500,
         "jit(decode_step)/while/body/transpose:")]
    assert pt.has_paths() and pt.size == len(host + device + other)


# ------------------------------------------- the traces recorded on the chip

FIXTURES = {"train": "train_v5e_2steps_named",
            "serve": "serve_v5e_ticks_named"}
NEW = {"train": ["backward_ms.train", "remat_ms.train", "optimizer_ms.train",
                 "head_loss_ms.train", "loop_host_share.train"],
       "serve": ["paged_attn_ms.serve", "decode_unscoped_ms.serve",
                 "paged_attn_roofline.serve",
                 "idle_stage_share.serve", "idle_sync_share.serve",
                 "idle_book_share.serve"]}


@pytest.fixture(scope="module", params=sorted(FIXTURES))
def recorded(request, tmp_path_factory):
    import xplane

    name = FIXTURES[request.param]
    path = str(tmp_path_factory.mktemp(name) / (name + ".xplane.pb"))
    with gzip.open(os.path.join(HERE, "data", name + ".xplane.pb.gz")) as src:
        with open(path, "wb") as dst:
            shutil.copyfileobj(src, dst)
    with open(os.path.join(HERE, "data", name + ".expected.json")) as f:
        want = json.load(f)
    cell = harness.load_cell(want["workload"])
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)["by_device_kind"][want["device_kind"]]
    ctx = harness.RunContext(
        cell=cell, dims=ref.Dims.from_config(cell.config), peaks=peaks,
        chips=1, window=(0.0, 1.0), counters={"steps": want["steps"]},
        spans={}, records=[], steps=[], trace=xplane.Trace(path))
    return request.param, ctx, want


def test_recorded_trace_gives_the_numbers_the_chip_run_printed(recorded):
    kind, ctx, want = recorded
    got = {m["name"]: reader(json.load(open(os.path.join(
        BENCH, "metrics", m["name"] + ".json")))["reader"])
        for m in ctx.cell.per_layer}
    for name in NEW[kind]:
        spec = json.load(open(os.path.join(BENCH, "metrics", name + ".json")))
        value = got[name].read(ctx, **spec["params"])
        assert value == pytest.approx(want["metrics"][name], rel=1e-9), name
    assert sorted({s.name for s in P.of(ctx).spans}) == want["span_names"]
    assert ctx.trace.busy_s() == pytest.approx(want["busy_s"])


def test_recorded_trace_holds_what_the_acceptance_asks(recorded):
    kind, ctx, want = recorded
    m = want["metrics"]
    if kind == "train":
        ph = P.step_phases(ctx)
        runs = ctx.trace.program_runs("jit_local_step")
        assert ph["step"] * len(runs) == pytest.approx(1e3 * sum(runs))
        # the operations themselves tile the run: what is between them is
        # under 1% of the step
        assert 0 <= ph["forward"] - ph["forward_ops"] < 0.01 * ph["step"]
        assert all(ph[k] > 0 for k in ("backward", "remat", "head_loss",
                                       "optimizer", "forward"))
        # the renamed kernels are still what `flash_attn_roofline.train`
        # matches, three to a layer and step plus the recomputed forward
        sec, n = ctx.trace.op_seconds("^flash_attention")
        assert n == 2 * 8 * 4
        assert {name.split(".")[0] for name, _, _ in ctx.trace.ops[0]
                if name.startswith("flash_attention")} == {
            "flash_attention_fwd", "flash_attention_dq",
            "flash_attention_dkv"}
    else:
        split = P.idle_split(ctx)
        assert sum(split[k] for k in ("stage", "sync", "book", "other")) == \
            pytest.approx(m["device_idle_share.serve"], abs=0.1)
        assert 0 < m["paged_attn_roofline.serve"] < 100
        parts = P.decode_parts(ctx, "decode_step")
        assert parts["paged"] == m["paged_attn_ms.serve"]
        assert parts["unscoped"] == m["decode_unscoped_ms.serve"]
        # medians of parts that sum exactly run by run
        assert parts["dense"] + parts["paged"] + parts["unscoped"] == \
            pytest.approx(m["decode_step_ms.serve"], rel=0.02)
        # under weights once over the peak bytes/s something is misplaced
        assert parts["dense"] > 4.978
        # the scan's own work on the stacked pool stands under `layers`
        assert any("/layers/while/body/" in p and P.decode_part_of(p) ==
                   "unscoped" for _, _, _, p in P.of(ctx).ops)
        pairs, unpaired = P.paired_decode_runs(ctx, P.of(ctx), "decode_step")
        assert pairs and unpaired <= 2
        for span, r0, r1, _ in pairs:
            assert span.parent.start < r0 < r1 < span.parent.end
            assert span.counters["live_positions"] > 0
