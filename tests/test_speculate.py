"""Speculative decoding + CoW prefix sharing on the paged serving engine.

The ISSUE 13 acceptance bars: greedy speculative streams are BITWISE
``generate()``'s at k ∈ {1, 3} for any draft, any admission order, with
and without CoW prefix sharing; the engine's compile set is exactly the
documented programs with zero retraces across the speculate on/off × k
grid; rejection sampling preserves the target distribution (empirical
acceptance matches the analytic ``Σ min(p, q)`` for a known p/q pair);
EOS emitted mid-window retires at the right token; a shared-prefix
workload's allocator peak drops. Engine-level greedy parity batteries
live in tests/test_generate.py next to the path they mirror.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddl25spring_tpu.config import LlamaConfig
from ddl25spring_tpu.models import llama
from ddl25spring_tpu.serving import (Engine, PagedKVConfig, Request,
                                     Scheduler, SpecConfig,
                                     reference_stream, run_serving,
                                     synthetic_workload)
from ddl25spring_tpu.serving.speculate import rejection_accept
from ddl25spring_tpu.telemetry.events import EventLog, read_events

CFG = LlamaConfig(vocab_size=97, dmodel=32, num_heads=4, n_layers=2,
                  ctx_size=32)
PAGED = PagedKVConfig(num_blocks=24, block_len=4, max_blocks_per_seq=8)


@pytest.fixture(scope="module")
def params():
    return llama.init_llama(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def draft_params():
    """A separately-weighted same-arch draft: disagrees with the target
    often (an adversarial acceptance rate), which is exactly what the
    bitwise bar must survive."""
    return llama.init_llama(jax.random.PRNGKey(7), CFG)


# ------------------------------------------------------- rejection sampling

def test_rejection_acceptance_matches_analytic():
    """The speculative-sampling identity, unit-tested without a model:
    with draft tokens ~ q and accept prob min(1, p/q), the per-position
    acceptance rate is analytically Σ_x min(p(x), q(x)) — empirical rate
    over many seeds must match, and the EMITTED first token (accepted
    draft or residual resample) must be distributed as p."""
    p0 = jnp.array([0.5, 0.3, 0.15, 0.05])
    q0 = jnp.array([0.2, 0.5, 0.2, 0.1])
    analytic = float(jnp.minimum(p0, q0).sum())        # 0.2+0.3+0.15+0.05
    k = 1
    p = jnp.stack([p0, p0])                            # [k+1, V]
    q = q0[None, :]                                    # [k, V]
    n = 4000
    rng = np.random.default_rng(0)
    # One vmapped dispatch over all n trials (keys PRNGKey(0..n-1), same
    # per-trial math as a host loop — rejection_accept is deterministic
    # per key): n host round trips would dominate the suite's wall time
    # for zero extra statistical power.
    drafts = rng.choice(4, size=n, p=np.asarray(q0)).astype(np.int32)
    keys = jax.jit(jax.vmap(jax.random.PRNGKey))(jnp.arange(n))
    a, corr = jax.vmap(
        lambda key, d: rejection_accept(key, p, q, d[None]))(
            keys, jnp.asarray(drafts))
    a = np.asarray(a)
    corr = np.asarray(corr)
    rate = a.sum() / n
    emitted = np.bincount(np.where(a > 0, drafts, corr), minlength=4)
    assert abs(rate - analytic) < 0.03, (rate, analytic)
    emp = emitted / n
    assert np.abs(emp - np.asarray(p0)).max() < 0.03, emp


def test_rejection_identical_distributions_always_accept():
    """p == q ⇒ min(1, p/q) == 1: acceptance is deterministic — the
    same-weights-draft trick that makes the CPU bench's
    tokens-per-dispatch bar exact."""
    p0 = jnp.array([0.4, 0.4, 0.2])
    p = jnp.stack([p0, p0, p0])
    q = jnp.stack([p0, p0])
    for seed in range(20):
        a, _ = rejection_accept(jax.random.PRNGKey(seed), p, q,
                                jnp.array([0, 2]))
        assert int(a) == 2, seed


def test_same_weights_stochastic_draft_accepts_everything(params):
    """Engine-level twin: a same-weights draft at temperature > 0 has
    p == q bitwise, so every round accepts all k proposals — acceptance
    rate exactly 1 in the report."""
    wl = [Request(rid="s0", prompt=(3, 5, 7), max_new=8, temperature=0.8,
                  seed=11),
          Request(rid="s1", prompt=(2, 9, 4, 1, 6), max_new=6,
                  temperature=0.6, seed=5)]
    rep = run_serving(params, CFG, PAGED, wl, num_slots=2, prefill_chunk=4,
                      speculate=SpecConfig(k=3, draft_params=params))
    assert rep.acceptance_rate == 1.0
    assert all(len(rep.records[r.rid].tokens) == r.max_new for r in wl)


# ------------------------------------------------------ compile contract

def test_spec_engine_compile_set_and_zero_retraces(params, draft_params):
    """Across the speculate on/off × k grid the compile count is exactly
    the documented program set — 2 plain (prefill + decode), 4 with
    speculation (prefill + verify + the draft's two; decode_step idles)
    — and NOTHING ever retraces: admission, raggedness, acceptance and
    horizon tails are data."""
    wl = synthetic_workload(seed=3, n_requests=8, rate_rps=500.0,
                            vocab_size=CFG.vocab_size,
                            prompt_lens=(2, 5, 9), max_news=(3, 5, 8),
                            temperatures=(0.0, 0.7))
    for spec, want_compiles in ((None, 2),
                                (SpecConfig(k=1, draft_params=draft_params),
                                 4),
                                (SpecConfig(k=3, draft_params=draft_params),
                                 4)):
        rep = run_serving(params, CFG, PAGED, wl, num_slots=3,
                          prefill_chunk=4, speculate=spec)
        assert rep.retraces == 0, spec
        assert rep.compiles == want_compiles, spec
        assert rep.aggregates["completed"] == len(wl)


def test_spec_tokens_per_dispatch_beats_plain(params):
    """The throughput bar at test scale, made deterministic: a single
    stream (no batching credit on either side) with a same-weights draft
    (greedy acceptance exactly 1) at k=3 — the plain engine pays one
    dispatch per token, the speculative one lands k+1 per verify
    dispatch. Multi-request workloads keep the same STREAMS (pinned in
    the parity battery); their concurrency mix differs because
    speculation drains slots faster, so the clean per-dispatch ratio is
    the single-stream one (the serving bench measures the loaded one)."""
    wl = [Request(rid="one", prompt=(2, 9, 4, 1), max_new=9)]
    plain = run_serving(params, CFG, PAGED, wl, num_slots=1,
                        prefill_chunk=8)
    spec = run_serving(params, CFG, PAGED, wl, num_slots=1,
                       prefill_chunk=8,
                       speculate=SpecConfig(k=3, draft_params=params))
    assert plain.records["one"].tokens == spec.records["one"].tokens
    assert spec.acceptance_rate == 1.0
    assert plain.tokens_per_dispatch == 1.0      # one token per dispatch
    assert spec.tokens_per_dispatch == 4.0       # k+1 per verify dispatch
    assert spec.decode_dispatches < plain.decode_dispatches


# -------------------------------------------------------- EOS mid-window

def test_eos_mid_window_retires_at_the_right_token(params):
    """An EOS landing INSIDE an accepted window (not at its edge) must
    retire the request at exactly that token: the stream is generate()'s
    truncated at the first EOS inclusive, post-EOS window tokens never
    existed, and the whole reservation frees at that boundary."""
    prompt = tuple(range(2, 8))
    full = reference_stream(params, CFG, PAGED,
                            Request(rid="p", prompt=prompt, max_new=12))
    eos = full[2]      # third token: inside the first k=3 verify window
    cut = full[:full.index(eos) + 1]
    assert len(cut) < 12
    eng = Engine(params, CFG, PAGED, 1, prefill_chunk=8,
                 speculate=SpecConfig(k=3, draft_params=params))
    sched = Scheduler(eng)
    sched.submit(Request(rid="r", prompt=prompt, max_new=12, eos_id=eos),
                 now=0.0)
    while sched.outstanding:
        sched.tick()
    assert sched.records["r"].tokens == cut
    assert eng.allocator.in_use == 0
    # Delivered-basis accounting: the dropped post-EOS window tail must
    # not inflate tokens-per-dispatch — Σ emitted over the v7 rounds is
    # exactly the delivered stream minus the prefill-sampled TTFT token,
    # and the engine's decode_tokens (the report's tokens_per_dispatch
    # numerator) matches.
    assert sum(r["emitted"] for r in sched.spec_rounds) == len(cut) - 1
    assert eng.decode_tokens == len(cut) - 1


def test_eos_mid_window_overlapping_max_new_retires_once(params):
    """Regression: one verify window can BOTH emit the EOS mid-window AND
    reach max_new at its last row (same-weights draft ⇒ acceptance 1, so
    k=3 + max_new=4 makes the whole horizon one window). The engine
    self-retires the slot while emitting the window tail; the scheduler's
    EOS path must see the already-freed slot and not retire it a second
    time (this crashed with ValueError before the liveness check)."""
    prompt = tuple(range(2, 8))
    full = reference_stream(params, CFG, PAGED,
                            Request(rid="p", prompt=prompt, max_new=4))
    eos = full[2]
    assert full.index(eos) == 2      # mid-window, non-final row — the
    cut = full[:3]                   # overlap this test exists to pin
    eng = Engine(params, CFG, PAGED, 1, prefill_chunk=8,
                 speculate=SpecConfig(k=3, draft_params=params))
    sched = Scheduler(eng)
    sched.submit(Request(rid="r", prompt=prompt, max_new=4, eos_id=eos),
                 now=0.0)
    while sched.outstanding:
        sched.tick()
    assert sched.records["r"].tokens == cut
    assert eng.allocator.in_use == 0


def test_hot_swap_lands_at_verify_boundary_bitwise(params, draft_params):
    """A weight swap mid-rollout under speculation lands between ticks —
    i.e. at a VERIFY boundary, so a round's draft proposals and its
    verification never mix target generations. Same-weights swap:
    bitwise invisible, zero retraces across it (the draft keeps its own
    weights)."""
    import jax as _jax

    prompt = tuple(range(2, 8))
    want = reference_stream(params, CFG, PAGED,
                            Request(rid="w", prompt=prompt, max_new=10))
    eng = Engine(params, CFG, PAGED, 1, prefill_chunk=8,
                 speculate=SpecConfig(k=3, draft_params=draft_params))
    sched = Scheduler(eng)
    sched.submit(Request(rid="r", prompt=prompt, max_new=10), now=0.0)
    ticks = 0
    swapped = False
    while sched.outstanding:
        sched.tick()
        ticks += 1
        if ticks == 2 and not swapped:
            # Mid-decode, between rounds: a fresh equal tree (host copy).
            clone = _jax.tree.map(lambda x: x + 0, params)
            sched.swap_weights(clone, version=1)
            swapped = True
    assert swapped and sched.records["r"].tokens == want
    assert sum(w.retraces for w in eng.watches()) == 0


# --------------------------------------------------- CoW prefix sharing

def _drive_pair(params, prompt, max_new, *, prefix_share, speculate=None,
                stagger=2, prompt_b=None):
    """Two requests (identical prompts unless ``prompt_b``), the second
    admitted mid-flight of the first; returns (streams, physical peak)."""
    eng = Engine(params, CFG, PAGED, 2, prefill_chunk=16,
                 prefix_share=prefix_share, speculate=speculate)
    s_a = eng.admit(np.asarray(prompt, np.int32), max_new)
    out = {s_a: []}
    s_b, steps = None, 0
    while eng.busy or s_b is None:
        if steps == stagger and s_b is None:
            s_b = eng.admit(np.asarray(prompt_b or prompt, np.int32),
                            max_new)
            out[s_b] = []
        for ev in eng.step():
            out[ev.slot].append(ev.token)
        steps += 1
    return (out[s_a], out[s_b]), eng.allocator.peak_in_use


def test_cow_prefix_sharing_drops_peak_and_stays_bitwise(params):
    """Two overlapping requests with an identical 3-block prompt: with
    prefix sharing the second maps the donor's prompt blocks read-only,
    so the physical allocator peak DROPS by the shared count while both
    streams stay bitwise generate()'s."""
    prompt = tuple(range(2, 14))                 # 12 tokens = 3 full blocks
    want = reference_stream(params, CFG, PAGED,
                            Request(rid="w", prompt=prompt, max_new=6))
    (a1, b1), peak_cow = _drive_pair(params, prompt, 6, prefix_share=True)
    (a0, b0), peak_plain = _drive_pair(params, prompt, 6,
                                       prefix_share=False)
    assert a1 == b1 == a0 == b0 == want
    assert peak_cow == peak_plain - 3            # 3 shared prompt blocks


def test_cow_divergent_tails_share_only_the_common_prefix(params):
    """Same 2-block prefix, different tails: the divergent tail lands in
    private blocks (the first divergent write copies — here, computes —
    into the sharer's own allocation), each stream bitwise its own
    generate()."""
    common = tuple(range(3, 11))                 # 8 tokens = 2 full blocks
    pa, pb = common + (20, 21), common + (30,)
    want_a = reference_stream(params, CFG, PAGED,
                              Request(rid="a", prompt=pa, max_new=5))
    want_b = reference_stream(params, CFG, PAGED,
                              Request(rid="b", prompt=pb, max_new=5))
    (a, b), peak = _drive_pair(params, pa, 5, prefix_share=True,
                               prompt_b=pb)
    assert a == want_a and b == want_b
    (_, _), peak_plain = _drive_pair(params, pa, 5, prefix_share=False,
                                     prompt_b=pb)
    assert peak == peak_plain - 2                # 2 shared prefix blocks


def test_cow_whole_prompt_shared_still_samples_first_token(params):
    """An identical prompt that is ENTIRELY full blocks: the sharer maps
    every prompt block and recomputes only the final chunk (writes to
    trash) to recover the first-token hidden state — stream bitwise."""
    prompt = tuple(range(4, 12))                 # 8 = 2 exact blocks
    want = reference_stream(params, CFG, PAGED,
                            Request(rid="w", prompt=prompt, max_new=4))
    (a, b), _ = _drive_pair(params, prompt, 4, prefix_share=True)
    assert a == b == want


def test_cow_with_speculation_bitwise(params, draft_params):
    """CoW and speculation compose: shared prompt blocks exist in BOTH
    pools (the donor's draft prefill wrote the draft copies), greedy
    streams stay bitwise through k=3 verify windows."""
    prompt = tuple(range(5, 17))                 # 3 full blocks
    want = reference_stream(params, CFG, PAGED,
                            Request(rid="w", prompt=prompt, max_new=6))
    spec = SpecConfig(k=3, draft_params=draft_params)
    (a, b), peak = _drive_pair(params, prompt, 6, prefix_share=True,
                               speculate=spec)
    assert a == b == want
    (_, _), peak_plain = _drive_pair(params, prompt, 6, prefix_share=False,
                                     speculate=spec)
    assert peak == peak_plain - 3


def test_cow_under_poisson_load_bitwise_and_saves_blocks(params):
    """A shared-prefix Poisson workload through the scheduler: every
    stream bitwise, physical peak strictly below the no-sharing run."""
    base = tuple(range(2, 10))                   # 2 full blocks shared
    wl = [Request(rid=f"r{i:02d}", prompt=base + (40 + i,), max_new=4,
                  arrival=0.002 * i) for i in range(8)]
    rep_cow = run_serving(params, CFG, PAGED, wl, num_slots=4,
                          prefill_chunk=8, prefix_share=True)
    rep_pln = run_serving(params, CFG, PAGED, wl, num_slots=4,
                          prefill_chunk=8)
    for r in wl:
        want = reference_stream(params, CFG, PAGED, r)
        assert rep_cow.records[r.rid].tokens == want, r.rid
        assert rep_pln.records[r.rid].tokens == want, r.rid
    assert rep_cow.peak_blocks_in_use < rep_pln.peak_blocks_in_use


# ------------------------------------- a verify window past the table

def test_speculation_at_the_horizon_of_a_full_width_reservation(
        params, draft_params):
    """The edge request reserves the table's full width (24 + 8 positions,
    eight blocks of four), and a late verify window (pos ≥ 29, k + 1 = 4
    rows) asks for a ninth block: in-program the clamp ``blk_idx =
    min(pos // bl, mb - 1)`` tops out at the table width and the overflow
    rows are live-masked to trash. Streams stay bitwise ``generate()``'s
    beside a short request, nothing retraces, and the engine's programs
    are exactly the documented five, each with a budget of one compile
    (``decode_step`` idles while speculation is on)."""
    wl = [Request(rid="short", prompt=(3, 5), max_new=4),
          Request(rid="edge", prompt=(4,) * 24, max_new=8)]
    eng = Engine(params, CFG, PAGED, 2, prefill_chunk=8,
                 speculate=SpecConfig(k=3, draft_params=draft_params))
    sched = Scheduler(eng)
    for q in wl:
        sched.submit(q)
    while sched.outstanding:
        sched.tick()
    for q in wl:
        assert sched.records[q.rid].tokens == reference_stream(
            params, CFG, PAGED, q), q.rid
    ws = eng.watches()
    assert [w.name for w in ws] == [
        "serving/prefill_chunk", "serving/decode_step",
        "serving/verify_step", "serving/draft_prefill",
        "serving/draft_decode"]
    assert all(w.max_caches == 1 and w.retraces == 0 for w in ws)
    assert [len(w.compiles) for w in ws] == [1, 0, 1, 1, 1]


# ----------------------------------------------------- telemetry (v7)

def test_speculate_events_emitted_and_schema_valid(params, tmp_path):
    """Every verify dispatch emits one strict-valid ``speculate`` event
    (schema v7) whose accounting reconciles with the report: Σ emitted
    == decode tokens, acceptance == accepted/proposed."""
    path = str(tmp_path / "events.jsonl")
    wl = synthetic_workload(seed=9, n_requests=5, rate_rps=300.0,
                            vocab_size=CFG.vocab_size, prompt_lens=(3, 6),
                            max_news=(4, 6), temperatures=(0.0,))
    with EventLog(path) as log:
        rep = run_serving(params, CFG, PAGED, wl, num_slots=2,
                          prefill_chunk=4, events=log,
                          speculate=SpecConfig(k=2, draft_params=params))
    events = read_events(path, strict=True)      # strict: v7 validates
    specs = [e for e in events if e["type"] == "speculate"]
    assert len(specs) == rep.decode_dispatches > 0
    assert sum(e["emitted"] for e in specs) == rep.decode_tokens
    assert sum(e["proposed"] for e in specs) == rep.spec_proposed
    assert sum(e["accepted"] for e in specs) == rep.spec_accepted
    assert all(e["k"] == 2 and e["rejected"] >= 0 for e in specs)


def test_bench_compare_tokens_per_dispatch_higher_is_better(tmp_path):
    """The speculative-decode trajectory row gates like a throughput row:
    a tokens-per-dispatch DROP is a regression, a rise is not."""
    import json

    from experiments.bench_compare import compare, lower_is_better

    assert not lower_is_better("tokens_per_dispatch")

    def write(name, value):
        p = tmp_path / name
        p.write_text(json.dumps({
            "metric": "serving_smoke",
            "rows": [{"metric": "tokens_per_dispatch", "value": value,
                      "platform": "cpu", "variant": "spec-k4"}]}) + "\n")
        return str(p)

    good = write("base_r01.json", 4.5)
    bad = write("cand.json", 2.0)
    _, regressions = compare([good], bad, max_regression_pct=10.0)
    assert regressions and "tokens_per_dispatch" in regressions[0]
    _, regressions = compare([good], write("cand2.json", 4.6),
                             max_regression_pct=10.0)
    assert not regressions


def test_slo_monitor_acceptance_floor():
    """A degenerate draft (acceptance → 0) breaches the acceptance-rate
    floor; a healthy one does not — and recovery re-arms the
    transition."""
    from experiments.slo_monitor import SLOConfig, replay_monitor

    def stream(rate):
        acc = int(round(10 * rate))
        return [{"schema": 7, "run_id": "r", "seq": i + 1, "t": float(i),
                 "type": "speculate", "proposed": 10, "accepted": acc,
                 "rejected": 10 - acc, "emitted": acc + 1, "k": 5,
                 "slots": 2} for i in range(40)]

    cfg = SLOConfig(window_s=10.0, min_acceptance_rate=0.5)
    bad = replay_monitor(stream(0.1), cfg)
    assert any(v["slo"] == "spec_acceptance_rate" for v in bad.violations)
    good = replay_monitor(stream(0.9), cfg)
    assert not good.violations
