"""The yardstick's own arithmetic: operation and byte counts against values
worked by hand, the plain reference against the program's forward pass and
its engine at a tiny size, and the trace reduction on a recorded trace."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
sys.path[:0] = [REPO, BENCH, os.path.join(BENCH, "readers")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import model_cost  # noqa: E402
import reference as ref  # noqa: E402


def dims_of(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return ref.Dims.from_config(json.load(f))


def test_parameter_counts_worked_by_hand():
    coder, llm = dims_of("deepseek-coder-1.3b"), dims_of("deepseek-llm-7b")
    # 4*2048^2 + 3*2048*5504 = 16,777,216 + 33,816,576
    assert model_cost.layer_params(coder.d, coder.ffn) == 50_593_792
    # 4*4096^2 + 3*4096*11008 = 67,108,864 + 135,266,304
    assert model_cost.layer_params(llm.d, llm.ffn) == 202_375_168
    # 8 layers + embedding and head of 32256 x 2048 each
    assert model_cost.model_params(coder) == 8 * 50_593_792 + 2 * 66_060_288
    assert model_cost.model_params(coder) == 536_870_912
    assert model_cost.model_params(llm) == 8 * 202_375_168 + 2 * 419_430_400
    # K and V, 8 layers, 4096 wide, 2 bytes
    assert model_cost.kv_bytes_per_position(llm) == 131_072
    assert model_cost.kv_bytes_per_position(coder) == 65_536


def test_training_flops_count_attention_causally():
    coder = dims_of("deepseek-coder-1.3b")
    t = 4096
    weights = 6 * (8 * 50_593_792 + 2048 * 32256)
    attn = 8 * 3 * 4 * 2048 * (t + 1) / 2
    got = model_cost.train_flops_per_token(coder, t)
    assert got == pytest.approx(weights + attn, rel=1e-12)
    assert got == pytest.approx(3.2276e9, rel=1e-3)
    # bench.py's train_step_flops_per_token counts 12*T*d a layer (no
    # mask): twice the causal count, so a utilisation from it is overstated
    unmasked = 8 * 3 * 4 * 2048 * t
    assert unmasked / attn == pytest.approx(2.0, rel=1e-3)
    flash = model_cost.flash_train_cost(coder, 4, t)
    assert flash["flops"] == 12 * 2048 * 4 * 8 * t * (t + 1) // 2
    assert flash["bytes"] == 12 * 4 * 8 * t * 2048 * 2


def test_decode_step_cost_is_weights_once_plus_live_cache():
    llm = dims_of("deepseek-llm-7b")
    c = model_cost.decode_step_cost(llm, live_positions=16 * 400, active=16)
    weights = 8 * 202_375_168 + 4096 * 102400
    assert c["bytes"] == weights * 2 + 16 * 400 * 131_072
    assert c["flops"] == 2.0 * weights * 16 + 4.0 * 4096 * 8 * 16 * 400
    # bandwidth-bound on a v5e by a wide margin
    assert c["bytes"] / 819e9 > 10 * c["flops"] / 197e12


# ---------------------------------------------------------------- reference

TINY = ref.Dims(vocab=512, d=64, heads=2, ffn=176, layers=2, eps=1e-6,
                theta=1e4)


def tiny_program_config(**kw):
    from ddl25spring_tpu.config import LlamaConfig

    return LlamaConfig(vocab_size=512, dmodel=64, num_heads=2, n_layers=2,
                       ffn_hidden=176, norm_eps=1e-6, rope_theta=1e4,
                       ctx_size=96, **kw)


def test_reference_agrees_with_the_programs_forward_and_loss_in_float32():
    """Same weights from the same seed (the reference's initialisation is
    the program's to an ulp: 1e-8), same float32 mathematics, another
    implementation: logits agree to 1e-5 of their largest value, the loss
    to 1e-5 relative and every gradient leaf to 1e-4 of its norm, all
    float32 re-association."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ddl25spring_tpu.models import llama

    cfg = tiny_program_config()
    seed = 2 ** 31 + 77
    w = ref.make_weights(seed, TINY, jnp.float32)
    p = llama.init_llama(jax.random.key(seed), cfg)
    for a, b in zip(jax.tree.leaves(w), jax.tree.leaves(p)):
        assert float(jnp.abs(a - b).max()) <= 1e-8
    toks = jax.random.randint(jax.random.key(1), (2, 96), 0, 512)
    want = llama.forward(p, toks, cfg)[0]
    got = ref.logits_at(w, ref.hidden(w, toks[0], TINY), TINY)
    assert float(jnp.abs(got - want).max()) <= 1e-5 * float(
        jnp.abs(want).max())
    loss, grads = ref.loss_and_grad(ref.make_row_grad(TINY, ref.REFERENCE),
                                    w, np.asarray(toks))
    want_loss, want_grads = jax.value_and_grad(
        lambda q: llama.forward_loss(q, toks, cfg))(p)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        assert float(jnp.linalg.norm(a - b)) <= 1e-4 * float(
            jnp.linalg.norm(b))


def test_engine_prefill_then_decode_agrees_with_the_reference_on_logits():
    """Prefill in chunks, then decoding through the paged cache, in float32:
    every served greedy token is the reference's best of its row, to within
    1e-4 of a logit (float32 re-association between a cached step and a
    full pass; the logits here spread over about 1)."""
    import jax.numpy as jnp
    import numpy as np
    from ddl25spring_tpu.serving.engine import Engine
    from ddl25spring_tpu.serving.kvcache import PagedKVConfig
    from ddl25spring_tpu.serving.scheduler import Request, Scheduler

    cfg = tiny_program_config(dtype="float32")
    w = ref.make_weights(5, TINY, jnp.float32)
    paged = PagedKVConfig(num_blocks=25, block_len=8, max_blocks_per_seq=12)
    sched = Scheduler(Engine(w, cfg, paged, 2, prefill_chunk=16))
    rng = np.random.default_rng(2)
    prompts = [tuple(int(t) for t in rng.integers(0, 512, n))
               for n in (37, 9, 50)]
    for i, pr in enumerate(prompts):
        sched.submit(Request(rid=str(i), prompt=pr, max_new=12))
    while sched.outstanding:
        sched.tick()
    gap = ref.make_gap_below_best(TINY)
    for i, pr in enumerate(prompts):
        served = sched.records[str(i)].tokens
        assert len(served) == 12
        toks = jnp.asarray(list(pr) + served, jnp.int32)
        gaps = np.asarray(gap(w, toks, toks[1:]))[len(pr) - 1:]
        assert gaps.max() <= 1e-4, gaps


# ------------------------------------------------------------ trace reduction

RECORDED = os.path.join(HERE, "data", "train_v5e_2steps.xplane.pb")


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_trace_reduction_on_the_recorded_trace():
    """A trace recorded on the v5e in PR 25, kept so that every later PR
    computes the same numbers from it."""
    import xplane

    with open(os.path.join(HERE, "data", "train_v5e_2steps.expected.json")) as f:
        want = json.load(f)
    t = xplane.Trace(RECORDED)
    assert t.chips == [0]
    assert t.busy_s() == pytest.approx(want["busy_s"], rel=1e-9)
    seconds, n = t.op_seconds(want["flash_pattern"])
    assert n == want["flash_events"]
    assert seconds == pytest.approx(want["flash_seconds"], rel=1e-9)
    runs = t.program_runs(want["program"])
    assert len(runs) == want["program_runs"]
    assert sum(runs) == pytest.approx(want["program_seconds"], rel=1e-9)
    assert [n for n, _ in t.top_ops(3)] == want["top3"]
    gaps = dict(t.idle_gaps(10))
    assert set(gaps) <= set(want["gap_names"])
    # busy and span are of one clock: the idle share cannot be negative
    assert t.busy_s() <= t.span_s() <= 1.001 * t.busy_s()
    # the kernel's events by run of the program: 4 a layer (forward, the
    # forward again under remat, dq, dk/dv), 8 layers, in each of 2 runs
    per_run = t.ops_inside(want["flash_pattern"], t.program_intervals())
    assert [n for _, n in per_run] == [32, 32]
    assert sum(s for s, _ in per_run) == pytest.approx(
        want["flash_seconds"], rel=1e-9)


def test_kernel_roofline_counts_work_by_runs_not_by_kernel_events():
    """The same trace read as a run of 2 steps and, with a kernel split in
    two (twice the events, the same seconds), the same share; a run cut by
    the trace's edge is left out of work and time alike."""
    import harness
    import xplane
    import xplane_kernel_roofline as reader

    coder = dims_of("deepseek-coder-1.3b")
    t = xplane.Trace(RECORDED)
    cell = harness.Cell("c", 1, "", "", {}, {"batch_per_chip": 4,
                        "seq_len": 4096}, {}, [], [])
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

    def share(trace, steps=2):
        ctx = harness.RunContext(cell=cell, dims=coder, peaks=peaks, chips=1,
                                 window=(0.0, 1.0), counters={"steps": steps},
                                 spans={}, records=[], steps=[], trace=trace)
        return reader.read(ctx, "^flash_attention", "flash_train")

    whole = share(t)
    one = model_cost.flash_train_cost(coder, 4, 4096)
    flash_s = sum(s for s, _ in t.ops_inside("^flash_attention",
                                             t.program_intervals()))
    assert whole == pytest.approx(
        100 * 2 * one["flops"] / 197e12 / flash_s, rel=1e-9)
    split = xplane.Trace.__new__(xplane.Trace)
    split.__dict__.update(t.__dict__)
    split.ops = {0: [ev for n, s, e in t.ops[0] for ev in (
        [(n, s, (s + e) // 2), (n, (s + e) // 2, e)]
        if n.startswith("flash_attention") else [(n, s, e)])]}
    assert share(split) == pytest.approx(whole, rel=1e-6)
    cut = xplane.Trace.__new__(xplane.Trace)
    cut.__dict__.update(t.__dict__)
    (_, r0, r1), second = t.modules[0][0], t.modules[0][1]
    mid = (r0 + r1) // 2
    cut.ops = {0: [ev for ev in t.ops[0] if ev[1] >= mid]}
    cut.modules = {0: [("jit_local_step(1)", mid, r1), second]}
    assert share(cut, steps=1) == pytest.approx(whole, rel=0.01)


def test_decode_roofline_pairs_a_run_with_the_tick_that_dispatched_it():
    import harness
    import xplane
    import decode_roofline as reader

    llm = dims_of("deepseek-llm-7b")
    t = xplane.Trace.__new__(xplane.Trace)
    t.ops, t.plane_names = {0: []}, []
    t.modules = {0: [("jit_decode_step(3)", 110, 150),
                     ("jit_prefill_chunk(2)", 205, 230),
                     ("jit_decode_step(3)", 240, 290),
                     ("jit_decode_step(3)", 400, 450)]}
    t.host = [("bench.tick#7", 100, 160), ("bench.tick#8", 200, 300)]
    steps = [{"tick": 6, "active": 1, "live_positions": 10 ** 6},
             {"tick": 7, "active": 16, "live_positions": 6400},
             {"tick": 8, "active": 16, "live_positions": 8000}]
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ctx = harness.RunContext(cell=None, dims=llm, peaks=peaks, chips=1,
                             window=(0.0, 1.0), counters={}, spans={},
                             records=[], steps=steps, trace=t)
    least = sum(model_cost.decode_step_cost(llm, p, 16)["bytes"] / 819e9
                for p in (6400, 8000))
    # the run at 400, under no tick, and the row of tick 6, with no run,
    # are left out of both sums
    assert reader.read(ctx, "decode_step") == pytest.approx(
        100 * least / 90e-9, rel=1e-9)


def test_union_and_gap_attribution_by_hand():
    import xplane

    assert xplane._union([(0, 5), (3, 8), (10, 12)]) == [(0, 8), (10, 12)]
    t = xplane.Trace.__new__(xplane.Trace)
    t.ops = {0: [("a", 0, 10), ("while.1", 0, 40), ("b", 30, 40),
                 ("b", 60, 70)]}
    t.modules = {0: [("jit_step(7)", 0, 40), ("jit_step(7)", 60, 70)]}
    t.host = [("bench.tick#3", 35, 62), ("bench.wait", 41, 59)]
    t.plane_names = []
    assert t.busy_s() == pytest.approx(50e-9)        # the while covers 0-40
    assert t.op_seconds("^b$") == (pytest.approx(20e-9), 2)
    assert t.top_ops(2) == [["b", pytest.approx(20e-9)],
                            ["a", pytest.approx(10e-9)]]
    assert t.program_runs("step") == [pytest.approx(40e-9),
                                      pytest.approx(10e-9)]
    # the gap 40-60 is covered by both; the tick covers all of it
    assert t.idle_gaps() == [["bench.tick", pytest.approx(20e-9)]]
    assert t.span() == (0, 70) and t.mark_at("bench.tick#", 40) == "bench.tick#3"
    assert t.mark_at("bench.tick#", 62) is None
