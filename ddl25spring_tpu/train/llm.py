"""End-to-end LLM training drivers.

`train_llm_dp` is the framework's minimum end-to-end slice: the reference's
whole DP gradient-aggregation script (lab/tutorial_1b/DP/gradient_aggr/
intro_DP_GA.py — N processes, gloo, per-iter flatten/allreduce) collapsed
into one jitted SPMD program reproducing its loss trajectory
(10.5 → ≈6 over 5000 iters, lab/out_b1_2.txt).
"""

from __future__ import annotations

import functools
import math
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ..config import LlamaConfig, ResilienceConfig, TrainConfig
from ..data.tokens import TokenStream, sharded_batches
from ..metrics import ResilienceStats
from ..models import llama
from ..parallel import dp, make_mesh, pp, tp
from ..resilience.preemption import PreemptionHandler
from ..telemetry import introspect
from ..telemetry.trace import Spans, Tracer
from ..tokenizers import load_tokenizer


@dataclass
class LLMTrainReport:
    losses: List[float] = field(default_factory=list)
    tokens_per_sec: float = 0.0
    steps: int = 0
    wall_time: float = 0.0
    # Resilience accounting: True if the loop exited early on a SIGTERM
    # force-save (re-running the same call resumes); counters cover guard
    # skips/rollbacks, checkpoint retries/fallbacks, and preemptions.
    # ``start_step`` is the stream position losses[0] corresponds to (the
    # resumed-from step; 0 for a fresh run) — ``iters - len(losses)`` is
    # WRONG for a preempted run, which ends early.
    preempted: bool = False
    start_step: int = 0
    resilience: Optional[ResilienceStats] = None
    # Elastic mode (resilience/elastic.py): one dict per replica-loss
    # recovery (RemeshRecord.as_dict — old/new world, path, seconds,
    # steps replayed), and the throughput measured on the final topology
    # (0.0 when no remesh happened or too little ran after the last one).
    remeshes: List[dict] = field(default_factory=list)
    post_remesh_tokens_per_sec: float = 0.0

    def tokens_per_sec_per_device(self, n_devices: int) -> float:
        return self.tokens_per_sec / max(n_devices, 1)


@functools.partial(jax.jit, static_argnames="cfg")
def _eval_batch_loss(params, batch, cfg: LlamaConfig):
    # Module-level + static cfg: periodic eval_llm calls from a train loop
    # hit the jit cache instead of recompiling a per-call closure.
    return llama.forward_loss(params, batch, cfg)


def eval_llm(params, model_cfg: LlamaConfig, *, n_batches: int = 16,
             batch_size: int = 8, skip: int = 0,
             tokenizer=None, seed: int = 1, stream=None) -> dict:
    """Held-out evaluation: mean next-token loss and perplexity over
    ``n_batches``. Parity-plus: the reference only ever prints train-batch
    loss (lab/tutorial_1b/primer/intro.py); an eval split is what lets a
    user see overfitting on the tiny corpus at all. Uses the fused head+CE,
    so no [B, T, V] logits materialize. Returns {"loss", "perplexity",
    "n_tokens"}.

    Held-out contract: on the synthetic fallback corpus a different
    ``seed`` IS a disjoint corpus (the generator is seed-parameterized), so
    the default seed=1 vs the trainers' seed=0 needs no skipping. For a
    file-backed corpus pass ``skip`` explicitly, PAST your training window
    (trainer shard i reads from sequence i·5000 for iters·batch_size
    sequences) — and note the stream cycles a short corpus, so disjointness
    holds only while skip + the eval span stays within one pass. For
    periodic evals with a nonzero skip, build the iterator once —
    ``it = iter(TokenStream(...))`` — and pass it via ``stream``: each call
    then continues it instead of re-tokenizing the whole skip window. (A
    raw TokenStream is also accepted but restarts — and re-pays the skip —
    on every call.)
    """
    tok = tokenizer or load_tokenizer()
    model_cfg = model_cfg.replace(vocab_size=tok.vocab_size)
    if stream is None:
        stream = TokenStream(tok, batch_size, model_cfg.ctx_size,
                             skip=skip, seed=seed)
    stream = iter(stream)  # no-op on iterators; accepts a raw TokenStream
    total = 0.0
    n_tokens = 0
    for _ in range(n_batches):
        batch = jnp.asarray(next(stream))
        total += float(_eval_batch_loss(params, batch, model_cfg))
        # The causal loss scores T-1 next-token positions per sequence.
        n_tokens += batch.shape[0] * (batch.shape[1] - 1)
    mean = total / n_batches
    return {"loss": mean, "perplexity": math.exp(min(mean, 30.0)),
            "n_tokens": n_tokens}


def _make_trainer_optimizer(train_cfg: TrainConfig):
    """TrainConfig.optimizer -> optimizer instance, shared by both trainers:
    "adam" is the reference's plain optax.adam; everything else dispatches
    through ops.adam.make_optimizer ("fused"/"pallas"/"master")."""
    if train_cfg.optimizer == "adam":
        return optax.adam(train_cfg.lr)
    from ..ops.adam import make_optimizer
    return make_optimizer(train_cfg.optimizer, train_cfg.lr)


def _setup_checkpoint(checkpoint_dir: Optional[str], state, iters: int,
                      log_fn: Callable[[str], None], *,
                      resilience: Optional[ResilienceConfig] = None,
                      stats: Optional[ResilienceStats] = None):
    """Shared resume preamble: open the orbax dir, restore the newest VALID
    step into ``state``'s layout (sharding-preserving; a corrupt latest step
    falls back to the previous one — checkpoint.py). Returns
    ``(ckpt, state, start_step, done)`` — ``done`` means the checkpoint is
    already at/past ``iters`` and there is nothing to train."""
    if checkpoint_dir is None:
        return None, state, 0, False
    from ..checkpoint import Checkpointer
    res = resilience or ResilienceConfig()
    ckpt = Checkpointer(checkpoint_dir, retry_attempts=res.retry_attempts,
                        retry_base_delay=res.retry_base_delay, stats=stats)
    start_step = 0
    if ckpt.latest_step() is not None:
        state = ckpt.restore(state)
        # The step that actually restored, NOT latest_step(): after a
        # corrupt-step fallback they differ, and resuming the loop from the
        # corrupt step's index would skip data the weights never saw.
        start_step = int(ckpt.restored_step)
        if start_step != int(ckpt.latest_step()):
            log_fn(f"latest step {int(ckpt.latest_step())} unreadable; "
                   f"fell back to step {start_step}")
        log_fn(f"resumed from step {start_step}")
    if start_step >= iters:
        log_fn(f"checkpoint already at step {start_step} >= iters {iters}; "
               "nothing to train")
        ckpt.close()
        return ckpt, state, start_step, True
    return ckpt, state, start_step, False


def _emit_manifest(telemetry, *, trainer: str, model_cfg, train_cfg,
                   mesh, start_step: int, step_fn, state, n_data: int,
                   steps_per_dispatch: int = 1, windowed: bool = False,
                   overlap_microbatches: int = 1,
                   preflight: Optional[dict] = None):
    """Open a telemetry run: one manifest event carrying the configuration
    and the step's static communication profile (telemetry/comm.py —
    measured by abstract tracing BEFORE the first real call, so the trace
    lands in the jit cache and costs nothing extra). Must run on the
    UNGUARDED step: StepGuard's host-side logic cannot be eval_shape'd.
    ``steps_per_dispatch > 1`` traces the K-step driver's [K, B, T] window:
    the profile covers one DISPATCH, per-step normalization alongside
    (CommProfile.as_dict). Returns the profile (None without telemetry)."""
    if telemetry is None:
        return None
    import dataclasses

    from ..telemetry import measure_comm
    comm_profile = profile = None
    try:
        batch_shape = (n_data * train_cfg.batch_size, train_cfg.seq_len)
        if steps_per_dispatch > 1 or windowed:
            # ``windowed``: the elastic loop drives the [K, B, T] window
            # step even at K=1, so the trace needs the leading step axis.
            batch_shape = (steps_per_dispatch,) + batch_shape
        batch_sds = jax.ShapeDtypeStruct(batch_shape, jnp.int32)
        profile = measure_comm(step_fn, state, batch_sds)
        comm_profile = None if profile is None else profile.as_dict(
            steps_per_dispatch=steps_per_dispatch,
            overlap_microbatches=overlap_microbatches)
    except Exception:
        pass                       # telemetry must never sink a trainer
    device = mesh.devices.flat[0]
    telemetry.events.manifest(
        trainer=trainer, jax_version=jax.__version__,
        platform=device.platform, device_kind=device.device_kind,
        n_devices=len(jax.devices()),
        mesh={k: int(v) for k, v in mesh.shape.items()},
        model_cfg=dataclasses.asdict(model_cfg),
        train_cfg=dataclasses.asdict(train_cfg),
        start_step=start_step, comm=comm_profile,
        # Which attention inner the step was built with, and in which
        # Pallas mode (llama.attention_path — the same call the model
        # dispatches on): a run that missed the compiled flash kernel
        # says so here.
        attention=llama.attention_path(model_cfg, train_cfg.seq_len),
        # Roofline denominators (introspect.device_peaks: the chip's
        # published peaks by device_kind, an unknown accelerator raises) —
        # recorded HERE so the jax-free readers (obs_report's attainment
        # section, slo_monitor's MFU floor) never have to re-derive them.
        peaks=introspect.device_peaks(device),
        # Preflight fit estimate (telemetry/memory.py, schema v9): the
        # predicted per-device byte budget, recorded next to the comm
        # profile so obs_report's memory section can table
        # preflight-vs-measured without re-deriving the model.
        **({} if preflight is None else {"preflight": preflight}))
    return profile


def _fault_extra(step_fn) -> dict:
    """StepGuard trip attribution (non-finite leaf paths of the rejected
    state) as extra ``fault``-event fields — and from the stream into the
    flight-recorder bundle that dumps on it. Shared by ``_run_loop`` and
    ``_run_elastic_loop`` so the two cannot drift."""
    pop = getattr(step_fn, "pop_trip", None)
    trip = pop() if callable(pop) else None
    return {"attribution": trip} if trip else {}


def _notify_checkpoint(hook, step: int, state, log_fn) -> None:
    """Checkpoint publication hook (the train→deploy seam,
    serving/deploy.py): called after every successful periodic/final
    ``ckpt.save`` with the step index and the live state, so a serving
    fleet can pick the weights up while this run keeps training. Guarded
    like telemetry — a broken publisher loses the publication, never the
    run. Shared by ``_run_loop`` and ``_run_elastic_loop`` so the two
    cannot drift."""
    if hook is None:
        return
    try:
        hook(step, state)
    except Exception as e:
        log_fn(f"checkpoint publication hook at step {step} failed "
               f"({type(e).__name__}: {e}); continuing")


def _phase_spans(tracer: Tracer, spans: Spans):
    """The loops' one way to time a host phase. ``_phase(name, parent,
    span_name, **counters)`` accumulates under ``name`` (``data``,
    ``dispatch``, ``sink``, ``checkpoint``) in ``spans``, through a child
    span ``span_name`` of ``parent`` in the event stream when there is one,
    and either way stands on the profiler's timeline as ``train.<name>``
    with ``counters`` (telemetry/trace.py)."""

    def _phase(name: str, parent, span_name: str, **counters):
        if parent is not None:
            return tracer.span(span_name, parent=parent.ctx, phase=name,
                               annotation="train." + name,
                               counters=counters)
        return spans(name, annotation="train." + name, **counters)

    return _phase


def _run_loop(step_fn, state, batches, train_cfg: TrainConfig, shard_fn, *,
              n_data: int, start_step: int, ckpt, checkpoint_every: int,
              loss_sink, sink_every: int, log_every: int, log_fn,
              warmup_steps_excluded: int,
              stats: Optional[ResilienceStats] = None,
              telemetry=None, steps_per_dispatch: int = 1,
              window_shard_fn=None, numerics=None,
              numerics_every: int = 0, compile_watch=None,
              injit_guard: bool = False, comm_profile=None,
              on_checkpoint=None, memory_meter=None) -> LLMTrainReport:
    """The training loop both trainers share: stream replay on resume,
    per-iteration loss sinking/logging, periodic + final checkpoint saves,
    and async-honest throughput accounting (the timer starts after
    ``warmup_steps_excluded`` post-resume steps, on a hard host sync).

    Self-healing (resilience/): when a checkpointer is attached, SIGTERM is
    caught at the next step boundary, a resumable checkpoint is force-saved,
    and the loop returns with ``report.preempted=True`` — re-running the
    same call resumes with data order preserved. A failed *periodic* save
    (after its internal retries) is logged and skipped rather than killing
    an otherwise healthy run; the final save still raises.

    Step indices are STREAM positions, not gradient-update counts: a
    StepGuard skip consumes its batch without learning from it, and a guard
    rollback extends that to the whole faulted window (the restored weights
    continue from the CURRENT stream position — the window's batches are
    deliberately not replayed, mirroring skip-and-count). That is what keeps
    resume deterministic: a checkpoint at step k always means "the stream
    has advanced k batches", so replay-to-k reproduces the data order no
    matter how many steps were skipped or rolled back.

    Loss buffering: device losses are held unsynced in a bounded pending
    buffer and flushed to host floats at sink boundaries (every
    ``sink_every`` steps and at the end) — the flush is where ``loss_sink``
    already forced a sync, so bounding the buffer costs no extra host round
    trips, and the old grow-O(iters) device-scalar list is gone.

    Chunked mode (``steps_per_dispatch`` = K > 1; DP trainer only): the
    step is a fused K-step driver (dp.make_multi_step /
    make_zero1_multi_step) taking a ``[K, B, T]`` window via
    ``window_shard_fn``, and every host-side decision quantizes to chunk
    edges, whose positions are absolute multiples of K so they are stable
    across resumes:

    - the per-step loss sequence comes back as the scan's stacked [K]
      output (bit-identical to per-step mode) and flushes through the same
      pending buffer, so ``loss_sink``/CSV rows land on the same step
      indices as per-step mode (delayed by at most a chunk);
    - periodic checkpoints save at the first chunk edge at/after each
      ``checkpoint_every`` boundary (exactly on it when K divides
      ``checkpoint_every``); SIGTERM force-saves at the next chunk edge;
      checkpoint step indices stay stream positions, so resume/replay is
      unchanged (a resume from a non-chunk-aligned step — e.g. a checkpoint
      written by a per-step run — realigns with one smaller first chunk);
    - StepGuard verdicts/skips and FaultPlan injection points are per
      DISPATCH: a skipped dispatch skips (consumes-not-learns) all K of its
      steps, and fault step indices count dispatches, not steps;
    - the throughput warmup exclusion quantizes up to the first chunk
      (``warmup_steps_excluded`` is treated as "at least", so compile time
      stays out of the timer either way);
    - the next chunk's host window is staged while the device runs the
      current one, so tokenization overlaps compute under async dispatch.

    Run-health introspection (``numerics`` = a
    telemetry.introspect.NumericsHandle, ``numerics_every`` > 0): the
    step's second output is ``(loss, NumericsSummary)`` — computed inside
    the same compiled dispatch, bitwise-invisible to losses/params — and
    the loop emits a ``numerics`` event every ``numerics_every`` steps
    (chunked mode samples the chunk's LAST step), plus one forced sample
    alongside every ``fault`` event so a flight-recorder bundle always
    carries the numerics state at the trip. Fault events additionally
    carry the StepGuard's ``pop_trip()`` attribution — the non-finite
    leaf PATHS of the rejected state.

    ``compile_watch`` (the step's introspect.CompileWatch, passed
    UNWRAPPED since the guard/fault layers don't delegate): a ``compute``
    span whose dispatch compiled (warmup, a tail-chunk shape) is stamped
    ``compiled=True`` so obs_report's attainment percentiles can exclude
    it — a compile-dominated interval is not an attainment sample.
    """
    report = LLMTrainReport()
    report.start_step = start_step
    report.resilience = stats if stats is not None else ResilienceStats()
    # In-jit guard accounting (``guard_nonfinite`` fused into the step —
    # ResilienceConfig.injit_guard): a skipped step's ONLY host-visible
    # trace is the non-advancing state.step counter, so snapshot it now
    # (post-restore) and diff once at the end — zero extra syncs per step.
    injit_step0 = (int(jax.device_get(state.step))
                   if injit_guard and hasattr(state, "step") else None)
    spans = Spans()  # phase accounting; absorbed into the registry at end
    # One tracing path (telemetry/trace.py): dispatch spans feed the SAME
    # phase accumulator they always did, and additionally land in the
    # event stream as a ``dispatch`` root with stage/compute/checkpoint/
    # sink children when telemetry is attached. Per-step mode samples at
    # the step-event cadence (a span per iteration would dominate the
    # stream); chunked mode traces every dispatch (already coarse).
    tracer = Tracer(telemetry.events if telemetry is not None else None,
                    phases=spans)
    _phase, sync = _phase_spans(tracer, spans), _Exchange(comm_profile)

    last_event_t = time.perf_counter()
    last_event_it = start_step - 1
    last_replay_beat = -math.inf  # first replayed batch always beats
    prev_counters = report.resilience.as_dict()
    last_saved = -1
    # First eligible step emits immediately; subsequent samples follow the
    # cadence. Tracked by stream position so chunked mode (which only sees
    # chunk edges) samples the first edge at/after each boundary.
    last_numerics_it = start_step - max(1, numerics_every)

    def _emit_numerics(it, aux, index=None):
        nonlocal last_numerics_it
        if aux is None or telemetry is None or numerics is None \
                or last_numerics_it == it:  # cadence + forced: one sample
            return
        try:
            telemetry.events.numerics(it=it,
                                      **numerics.event_fields(aux,
                                                              index=index))
        except Exception:
            pass                   # introspection must never sink the run
        last_numerics_it = it

    tokens_per_step = n_data * train_cfg.batch_size * train_cfg.seq_len
    t_start = None
    excluded_steps = warmup_steps_excluded
    pending = []  # (first step index, device loss scalar or [k] vector):
    #               bounded — flushed to host floats at sink boundaries; a
    #               float() per step would serialize dispatch and deflate
    #               throughput, an unbounded device list would leak buffers.

    def _flush_losses():
        for it0, ls in pending:
            for j, v in enumerate(np.atleast_1d(np.asarray(ls))):
                i, v = it0 + j, float(v)
                report.losses.append(v)
                if loss_sink is not None and (i % sink_every == 0
                                              or i == train_cfg.iters - 1):
                    loss_sink(i, v)
        pending.clear()

    # Installed with or without a checkpointer: an uncheckpointed run can't
    # force-save, but it still exits the loop cleanly on SIGTERM (counters
    # and report intact) instead of dying mid-step — a chaos run without
    # --checkpoint-dir must demo graceful preemption, not a hard kill.
    preempt = PreemptionHandler()
    last_it = start_step - 1

    def _force_save(at: int) -> None:
        # Force-save a resumable checkpoint BEFORE dying: the next
        # invocation restores step ``at`` and replays the stream.
        # A checkpoint of THIS run's lineage at ``at`` exists only
        # if this loop saved it (last_saved) or resumed from it
        # (start_step); any other on-disk step ``at`` is a stale —
        # possibly the corrupt — remnant of a pre-fallback lineage
        # that the save must replace, not trust (latest_step() alone
        # can't tell these apart after a corrupt-latest fallback).
        if ckpt is not None:
            if at not in (last_saved, start_step):
                ckpt.save(at, state, force=True, overwrite=True)
            ckpt.wait()
        report.preempted = True
        report.resilience.preemptions += 1
        log_fn(f"preempted at iter {at}: checkpoint "
               f"{'force-saved' if ckpt is not None else 'not saved'}"
               f"{'' if ckpt is not None else ' (no checkpoint dir)'}")

    if steps_per_dispatch <= 1:
        with preempt:
            for it in range(train_cfg.iters):
                droot = (tracer.start("dispatch", trace="train", it=it,
                                      phase=False)
                         if (telemetry is not None and it >= start_step
                             and it % telemetry.step_every == 0) else None)
                with _phase("data", droot, "stage"):
                    host_batch = next(batches).reshape(
                        n_data * train_cfg.batch_size, train_cfg.seq_len)
                if it < start_step:
                    # Replaying IS progress, but a beat per replayed batch
                    # would add thousands of temp-file renames to an
                    # otherwise host-only fast-forward; throttle to well
                    # under the watchdog's polling granularity.
                    if telemetry is not None:
                        now = time.perf_counter()
                        if now - last_replay_beat >= 0.5:
                            telemetry.heartbeat.beat(step=it, phase="replay")
                            last_replay_beat = now
                    continue  # resume: replay stream, preserving data order
                if preempt.requested:
                    if droot is not None:
                        droot.end(preempted=True)
                    _force_save(it)
                    break
                last_it = it
                t_iter = time.perf_counter()
                n_compiles = (len(compile_watch.compiles)
                              if compile_watch is not None else 0)
                with sync.dispatch(_phase, droot, it) as csp:
                    state, out = step_fn(state, shard_fn(host_batch))
                    if (droot is not None and compile_watch is not None
                            and len(compile_watch.compiles) > n_compiles):
                        csp.attrs["compiled"] = True
                loss, naux = introspect.split_step_output(out)
                if it + 1 == start_step + warmup_steps_excluded:
                    float(loss)  # hard sync before starting the timer
                    t_start = time.perf_counter()
                    # Re-baseline the step-event window too: the time before
                    # this sync is compile + (on resume) stream replay, which
                    # would otherwise land in the first window's dt_s and
                    # dominate obs_report's step-time percentiles.
                    last_event_t, last_event_it = t_start, it
                pending.append((it, loss))
                if it % sink_every == 0 or it == train_cfg.iters - 1:
                    with _phase("sink", droot, "sink"):
                        _flush_losses()  # sink boundary: host ring update
                if log_every and it % log_every == 0:
                    log_fn(f"iter {it}: loss {float(loss):.4f}")
                if telemetry is not None:
                    # Host-side iteration wall time: dispatch + host work,
                    # NOT device completion (no sync; under async dispatch
                    # read the honest throughput from tokens_per_sec / the
                    # step events).
                    telemetry.registry.observe("host_iter_s",
                                               time.perf_counter() - t_iter)
                    telemetry.heartbeat.beat(step=it)
                    if (it % telemetry.step_every == 0
                            or it == train_cfg.iters - 1):
                        now = time.perf_counter()
                        extra = {}
                        if t_start is None:
                            # Pre-baseline window: dt_s still contains
                            # one-time compile/replay. Keep the event (its
                            # loss matters) but flag it so readers exclude
                            # it from step-time distributions (obs_report
                            # does).
                            extra["warmup"] = True
                        telemetry.events.step(
                            it=it, loss=float(loss),  # the documented sync
                            dt_s=now - last_event_t,
                            steps=it - last_event_it, **extra)
                        last_event_t, last_event_it = now, it
                        if memory_meter is not None:
                            # Memory census rides the step-event cadence:
                            # host-side byte math only (schema v9), no
                            # device sync beyond the loss read above.
                            memory_meter.sample(it=it)
                    if (naux is not None
                            and it - last_numerics_it >= numerics_every):
                        _emit_numerics(it, naux)
                    delta = report.resilience.delta(prev_counters)
                    if delta:
                        # Forced numerics sample + guard attribution ride
                        # ahead of / on the fault event, so the flight
                        # recorder's dump (triggered by it) carries both.
                        _emit_numerics(it, naux)
                        telemetry.events.fault(counters=delta, it=it,
                                               **_fault_extra(step_fn))
                        prev_counters = report.resilience.as_dict()
                if ckpt is not None and (it + 1) % checkpoint_every == 0:
                    try:
                        # overwrite: after a corrupt-latest fallback resume
                        # the loop re-treads step indices the dead lineage
                        # already wrote (start_step < it+1 <= old latest),
                        # and those stale entries must not survive as
                        # restore candidates.
                        with _phase("checkpoint", droot, "checkpoint"):
                            ckpt.save(it + 1, state, overwrite=True)
                        last_saved = it + 1
                        _notify_checkpoint(on_checkpoint, it + 1, state,
                                           log_fn)
                    except Exception as e:
                        log_fn(f"periodic checkpoint at {it + 1} failed "
                               f"after retries ({type(e).__name__}: {e}); "
                               "continuing")
                if droot is not None:
                    droot.end()
    else:
        # ------------------------------------------------- chunked mode
        # NOTE: _run_elastic_loop mirrors this block (plus the recovery
        # path) and its zero-fault contract is BITWISE equality with it —
        # a cadence/staging/checkpoint-edge change here must land there
        # too (tests/test_elastic.py pins the equality).
        K = steps_per_dispatch
        chunks = []
        edge = start_step
        while edge < train_cfg.iters:
            nxt = min(train_cfg.iters, (edge // K + 1) * K)
            chunks.append((edge, nxt))
            edge = nxt

        def _window(it0, it1, parent=None):
            with _phase("data", parent, "stage"):
                return np.stack([
                    next(batches).reshape(n_data * train_cfg.batch_size,
                                          train_cfg.seq_len)
                    for _ in range(it1 - it0)])

        staged = None
        last_flush_edge = start_step
        with preempt:
            for rep in range(start_step):   # resume: replay the stream
                next(batches)
                if telemetry is not None:
                    now = time.perf_counter()
                    if now - last_replay_beat >= 0.5:
                        telemetry.heartbeat.beat(step=rep, phase="replay")
                        last_replay_beat = now
            for ci, (it0, it1) in enumerate(chunks):
                if preempt.requested:
                    _force_save(it0)
                    break
                # One trace root per dispatch (the chunk IS the dispatch
                # granularity); children cover this chunk's host work,
                # including the NEXT window's staging — that overlap
                # landing inside the compute-bound interval is exactly
                # what the timeline should show.
                droot = (tracer.start("dispatch", trace="train", it=it0,
                                      steps=it1 - it0, phase=False)
                         if telemetry is not None else None)
                window = (staged if staged is not None
                          else _window(it0, it1, droot))
                staged = None
                t_iter = time.perf_counter()
                n_compiles = (len(compile_watch.compiles)
                              if compile_watch is not None else 0)
                with sync.dispatch(_phase, droot, it0) as csp:
                    state, out = step_fn(state, window_shard_fn(window))
                    if (droot is not None and compile_watch is not None
                            and len(compile_watch.compiles) > n_compiles):
                        csp.attrs["compiled"] = True
                losses, naux = introspect.split_step_output(out)
                # Stage the NEXT chunk's host window while the device runs
                # this one: under async dispatch the tokenize/stack work
                # overlaps compute instead of serializing after it.
                if ci + 1 < len(chunks):
                    staged = _window(*chunks[ci + 1], droot)
                last_it = it1 - 1
                first_chunk = t_start is None
                pending.append((it0, losses))
                if log_every:
                    for i in range(it0, it1):
                        if i % log_every == 0:
                            log_fn(f"iter {i}: "
                                   f"loss {float(losses[i - it0]):.4f}")
                if telemetry is not None:
                    telemetry.registry.observe(  # per DISPATCH (K steps)
                        "host_iter_s", time.perf_counter() - t_iter)
                    telemetry.heartbeat.beat(step=last_it)
                    if (last_it - last_event_it >= telemetry.step_every
                            or it1 == train_cfg.iters):
                        now = time.perf_counter()
                        extra = {"steps_per_dispatch": it1 - it0}
                        if first_chunk:
                            extra["warmup"] = True  # dt contains compile
                        telemetry.events.step(
                            it=last_it, loss=float(losses[-1]),
                            dt_s=now - last_event_t,
                            steps=last_it - last_event_it, **extra)
                        last_event_t, last_event_it = now, last_it
                        if memory_meter is not None:
                            # Chunk-edge memory census (host byte math
                            # only; same cadence as the step event).
                            memory_meter.sample(it=last_it)
                    if (naux is not None
                            and last_it - last_numerics_it >= numerics_every):
                        # Chunk-edge sampling: the stacked [K] summary's
                        # LAST step stands for the chunk.
                        _emit_numerics(last_it, naux, index=-1)
                    delta = report.resilience.delta(prev_counters)
                    if delta:
                        _emit_numerics(last_it, naux, index=-1)
                        telemetry.events.fault(counters=delta, it=last_it,
                                               **_fault_extra(step_fn))
                        prev_counters = report.resilience.as_dict()
                if first_chunk:
                    # Warmup exclusion quantized to the first chunk edge:
                    # compile + (on resume) replay land before this sync.
                    float(losses[-1])
                    t_start = time.perf_counter()
                    excluded_steps = it1 - it0
                    last_event_t, last_event_it = t_start, last_it
                if (it1 - last_flush_edge >= sink_every
                        or it1 == train_cfg.iters):
                    with _phase("sink", droot, "sink"):
                        _flush_losses()  # sink boundary (chunk-edge quantized)
                    last_flush_edge = it1
                if ckpt is not None and (it1 // checkpoint_every
                                         ) > (it0 // checkpoint_every):
                    try:
                        with _phase("checkpoint", droot, "checkpoint"):
                            ckpt.save(it1, state, overwrite=True)
                        last_saved = it1
                        _notify_checkpoint(on_checkpoint, it1, state, log_fn)
                    except Exception as e:
                        log_fn(f"periodic checkpoint at {it1} failed after "
                               f"retries ({type(e).__name__}: {e}); "
                               "continuing")
                if droot is not None:
                    droot.end()
    if ckpt is not None:
        if not report.preempted and train_cfg.iters != last_saved:
            ckpt.save(train_cfg.iters, state, force=True, overwrite=True)
            _notify_checkpoint(on_checkpoint, train_cfg.iters, state, log_fn)
        ckpt.close()
    _flush_losses()  # preempted/odd-tail runs: drain whatever is buffered
    report.steps = (last_it + 1 if report.preempted else train_cfg.iters) \
        - start_step
    if injit_step0 is not None:
        # Executed steps minus step-counter advances = fused-guard skips
        # (the select-back keeps state.step frozen on a bad step). One
        # scalar sync, after the loop — the skip itself never left jit.
        good = int(jax.device_get(state.step)) - injit_step0
        report.resilience.skipped_steps += max(0, report.steps - good)
    if t_start is not None and report.steps > excluded_steps:
        report.wall_time = time.perf_counter() - t_start
        timed = report.steps - excluded_steps
        report.tokens_per_sec = tokens_per_step * timed / report.wall_time
    if telemetry is not None:
        telemetry.registry.absorb_spans(spans)
        telemetry.registry.absorb_resilience(report.resilience)
        telemetry.events.run_end(
            steps=report.steps, start_step=start_step,
            preempted=report.preempted,
            tokens_per_sec=report.tokens_per_sec, wall_s=report.wall_time,
            metrics=telemetry.registry.snapshot())
        telemetry.heartbeat.beat(step=last_it + 1, phase="done")
    return report


def _run_elastic_loop(controller, step_fn, state, batches,
                      train_cfg: TrainConfig, *, n_data: int,
                      start_step: int, ckpt, checkpoint_every: int,
                      loss_sink, sink_every: int, log_every: int, log_fn,
                      warmup_steps_excluded: int,
                      stats: Optional[ResilienceStats] = None,
                      telemetry=None, steps_per_dispatch: int = 1,
                      window_shard_fn=None,
                      on_checkpoint=None, scale_hook=None,
                      memory_meter=None) -> LLMTrainReport:
    """The chunked training loop (``_run_loop`` chunked mode) with a
    replica-loss recovery path threaded through it: every dispatch runs
    under a ``ReplicaLossError``/``ReplicaReturnSignal`` catch, every
    chunk edge feeds the controller's host-RAM mirror, and a caught loss
    (or return) drains the in-flight work, hands the world to
    ``ElasticController.recover`` (``grow``) and swaps in the new
    mesh/state/step/stream before continuing. ``scale_hook(it, world)``
    is additionally polled at every chunk edge; a non-None target world
    triggers ``ElasticController.resize`` — the autoscaler's
    capacity-change path, zero steps lost (the resize snapshots the
    just-drained state at the edge itself).

    Zero-fault contract: the loss trajectory is bitwise the non-elastic
    path's — the step functions come from the same factories, the windows
    from the same stream arithmetic; the elastic extras (mirror sync at
    chunk edges, the try/except) never touch the numerics
    (tests/test_elastic.py pins it).

    Bookkeeping under recovery: step indices stay stream positions. A
    recovery that rewinds to mirror/checkpoint position ``m < failed_at``
    re-trains steps ``m..`` on the new topology with the new topology's
    stream — the loss record and CSV rows for those positions are
    REWRITTEN (``report.losses`` truncates to ``m``; sink rows follow the
    resume convention: later rows win), because the new-world trajectory
    is the run's trajectory from ``m`` on. Chunk edges stay absolute
    multiples of K, so a non-aligned recovery point realigns with one
    smaller chunk exactly like a non-aligned resume. Throughput:
    ``tokens_per_sec`` counts each topology's tokens at its own width
    (wall time includes recovery, honestly); ``post_remesh_tokens_per_sec``
    times the final topology from its first post-recovery synced chunk."""
    from ..resilience.faults import ReplicaLossError, ReplicaReturnSignal

    report = LLMTrainReport()
    report.start_step = start_step
    report.resilience = stats if stats is not None else ResilienceStats()
    spans = Spans()
    tracer = Tracer(telemetry.events if telemetry is not None else None,
                    phases=spans)
    _phase = _phase_spans(tracer, spans)

    K = max(1, steps_per_dispatch)
    last_event_t = time.perf_counter()
    last_event_it = start_step - 1
    last_replay_beat = -math.inf
    prev_counters = report.resilience.as_dict()
    last_saved = -1
    t_start = None
    excluded_steps = warmup_steps_excluded
    timed_tokens = 0.0            # tokens after the warmup sync, per-width
    phase_t0 = None               # current-topology timer (post-remesh)
    phase_tokens = 0.0
    pending = []                  # (first step index, [k] device losses)

    def _flush_losses():
        for it0, ls in pending:
            for j, v in enumerate(np.atleast_1d(np.asarray(ls))):
                i, v = it0 + j, float(v)
                report.losses.append(v)
                if loss_sink is not None and (i % sink_every == 0
                                              or i == train_cfg.iters - 1):
                    loss_sink(i, v)
        pending.clear()

    def _window(it0, it1, parent=None):
        # Reads n_data/batches from the enclosing frame so a recovery's
        # rebinding re-points it at the survivors' stream automatically.
        with _phase("data", parent, "stage"):
            return np.stack([
                next(batches).reshape(n_data * train_cfg.batch_size,
                                      train_cfg.seq_len)
                for _ in range(it1 - it0)])

    preempt = PreemptionHandler()
    last_it = start_step - 1
    staged = None                   # (first step index, host window)
    edge = start_step

    def _swap(resume):
        # Install a Resume's world — shared by the fault paths (loss /
        # return) and the scale_hook resize. Step indices stay stream
        # positions: the record truncates to the resume point ``m`` and
        # every cursor rewinds with it (a fault path can land below the
        # current edge; a resize lands exactly ON it and truncates
        # nothing).
        nonlocal n_data, state, step_fn, window_shard_fn, batches, \
            last_it, last_flush_edge, last_event_t, last_event_it, \
            phase_t0, phase_tokens, staged, edge
        n_data = resume.n_data
        state, step_fn = resume.state, resume.step_fn
        window_shard_fn, batches = resume.window_shard_fn, resume.batches
        m = resume.step
        pending[:] = [p for p in pending if p[0] < m]
        # The loss record indexes from report.start_step; a slow-path
        # rewind can land BELOW it (digest-failed newest step → older
        # checkpoint), in which case the record now begins at m and
        # start_step must follow or every consumer (hw1b's sink rows,
        # report.steps) mislabels by the gap.
        del report.losses[max(0, m - report.start_step):]
        report.start_step = min(report.start_step, m)
        report.remeshes.append(resume.record.as_dict())
        # Rewind the progress cursor too: steps in [m, detected_at) were
        # discarded with the old topology, and a preemption landing
        # before they are re-trained must report/force-save position m,
        # not the rolled-back high-water mark.
        last_it = m - 1
        last_flush_edge = min(last_flush_edge, m)
        last_event_t = time.perf_counter()
        last_event_it = m - 1
        phase_t0, phase_tokens = None, 0.0
        staged = None               # old width, old stream
        edge = m

    def _force_save(at: int) -> None:
        if ckpt is not None:
            if at not in (last_saved, start_step):
                ckpt.save(at, state, force=True, overwrite=True)
            ckpt.wait()
        report.preempted = True
        report.resilience.preemptions += 1
        log_fn(f"preempted at iter {at}: checkpoint "
               f"{'force-saved' if ckpt is not None else 'not saved'}"
               f"{'' if ckpt is not None else ' (no checkpoint dir)'}")

    with preempt:
        for rep in range(start_step):   # resume: replay the stream
            next(batches)
            if telemetry is not None:
                now = time.perf_counter()
                if now - last_replay_beat >= 0.5:
                    telemetry.heartbeat.beat(step=rep, phase="replay")
                    last_replay_beat = now
        # Seed the mirror with the initial state: a loss on the very
        # first dispatch must be recoverable without a checkpoint.
        controller.note_edge(start_step, state)
        edge = start_step
        staged = None               # (first step index, host window)
        last_flush_edge = start_step
        dispatch_idx = 0
        while edge < train_cfg.iters:
            if preempt.requested:
                _force_save(edge)
                break
            it0, it1 = edge, min(train_cfg.iters, (edge // K + 1) * K)
            droot = (tracer.start("dispatch", trace="train", it=it0,
                                  steps=it1 - it0, phase=False)
                     if telemetry is not None else None)
            if staged is not None and staged[0] == it0:
                window = staged[1]
            else:
                window = _window(it0, it1, droot)
            staged = None
            t_iter = time.perf_counter()
            this_dispatch, dispatch_idx = dispatch_idx, dispatch_idx + 1
            try:
                with _phase("dispatch", droot, "compute", it=it0):
                    state, losses = step_fn(state,
                                            window_shard_fn(window))
            except (ReplicaLossError, ReplicaReturnSignal) as err:
                grow = isinstance(err, ReplicaReturnSignal)
                if droot is not None:
                    droot.end(**{"replica_return" if grow
                                 else "replica_loss": True})
                with spans("recover"):
                    # Drain: settle in-flight work AND keep the host
                    # copies — the device arrays belong to the old
                    # topology, and a flush after recovery must not
                    # re-read buffers a real backend failure took away.
                    pending[:] = [(i0, np.asarray(ls))
                                  for i0, ls in pending]
                    handle = controller.grow if grow else controller.recover
                    resume = handle(err, failed_at=it0,
                                    dispatch=this_dispatch)
                _swap(resume)
                continue
            tokens_per_step = (n_data * train_cfg.batch_size
                               * train_cfg.seq_len)
            last_it = it1 - 1
            first_chunk = t_start is None
            pending.append((it0, losses))
            if it1 < train_cfg.iters:
                # Stage the NEXT chunk's host window while the device runs
                # this one (same overlap as the non-elastic chunked loop);
                # a recovery discards it — wrong width, wrong stream.
                nxt = min(train_cfg.iters, (it1 // K + 1) * K)
                staged = (it1, _window(it1, nxt, droot))
            if log_every:
                for i in range(it0, it1):
                    if i % log_every == 0:
                        log_fn(f"iter {i}: "
                               f"loss {float(losses[i - it0]):.4f}")
            if telemetry is not None:
                telemetry.registry.observe(
                    "host_iter_s", time.perf_counter() - t_iter)
                telemetry.heartbeat.beat(step=last_it)
                if (last_it - last_event_it >= telemetry.step_every
                        or it1 == train_cfg.iters):
                    now = time.perf_counter()
                    extra = {"steps_per_dispatch": it1 - it0}
                    if first_chunk or (report.remeshes
                                       and phase_t0 is None):
                        extra["warmup"] = True  # compile / re-mesh compile
                    telemetry.events.step(
                        it=last_it, loss=float(losses[-1]),
                        dt_s=now - last_event_t,
                        steps=last_it - last_event_it, **extra)
                    last_event_t, last_event_it = now, last_it
                    if memory_meter is not None:
                        # Chunk-edge census; the elastic extras — mirror
                        # bytes and the current world — make grow/shrink
                        # memory deltas visible in the event stream.
                        memory_meter.sample(
                            it=last_it, world=n_data,
                            mirror_bytes=controller.mirror_bytes())
                delta = report.resilience.delta(prev_counters)
                if delta:
                    telemetry.events.fault(counters=delta, it=last_it,
                                           **_fault_extra(step_fn))
                    prev_counters = report.resilience.as_dict()
            if first_chunk:
                float(losses[-1])   # sync: compile/replay stay untimed
                t_start = time.perf_counter()
                excluded_steps = it1 - it0
                last_event_t, last_event_it = t_start, last_it
                if not report.remeshes:
                    phase_t0 = t_start
            elif phase_t0 is None:
                # First completed chunk on a new topology: its dt is
                # dominated by the re-mesh recompile; sync and start the
                # post-remesh throughput window after it.
                float(losses[-1])
                phase_t0 = time.perf_counter()
            else:
                timed_tokens += (it1 - it0) * tokens_per_step
                phase_tokens += (it1 - it0) * tokens_per_step
            controller.note_edge(it1, state)   # last-good mirror refresh
            if (it1 - last_flush_edge >= sink_every
                    or it1 == train_cfg.iters):
                with _phase("sink", droot, "sink"):
                    _flush_losses()
                last_flush_edge = it1
            if ckpt is not None and (it1 // checkpoint_every
                                     ) > (it0 // checkpoint_every):
                try:
                    with _phase("checkpoint", droot, "checkpoint"):
                        ckpt.save(it1, state, overwrite=True)
                    last_saved = it1
                    _notify_checkpoint(on_checkpoint, it1, state, log_fn)
                except Exception as e:
                    log_fn(f"periodic checkpoint at {it1} failed after "
                           f"retries ({type(e).__name__}: {e}); "
                           "continuing")
            if scale_hook is not None and it1 < train_cfg.iters:
                # Capacity-change seam (resilience/autoscale.py): the
                # hook sees the just-drained edge; a differing target
                # world re-meshes HERE — state snapshotted at this exact
                # position, so nothing is replayed and nothing is lost.
                target = scale_hook(it1, n_data)
                if target is not None and int(target) != n_data:
                    with spans("recover"):
                        pending[:] = [(i0, np.asarray(ls))
                                      for i0, ls in pending]
                        resume = controller.resize(
                            int(target), state=state, at_step=it1,
                            dispatch=dispatch_idx - 1)
                    if resume is not None:
                        if droot is not None:
                            droot.end(scaled=True)
                        _swap(resume)
                        continue
            if droot is not None:
                droot.end()
            edge = it1
    if ckpt is not None:
        if not report.preempted and train_cfg.iters != last_saved:
            ckpt.save(train_cfg.iters, state, force=True, overwrite=True)
            _notify_checkpoint(on_checkpoint, train_cfg.iters, state, log_fn)
        ckpt.close()
    _flush_losses()
    t_end = time.perf_counter()
    # report.start_step, not the local: a slow-path recovery may have
    # rewound the record's origin below the resumed-from step.
    report.steps = (last_it + 1 if report.preempted else train_cfg.iters) \
        - report.start_step
    if t_start is not None and report.steps > excluded_steps:
        report.wall_time = t_end - t_start
        report.tokens_per_sec = timed_tokens / max(report.wall_time, 1e-9)
    if report.remeshes and phase_t0 is not None and phase_tokens > 0:
        report.post_remesh_tokens_per_sec = (
            phase_tokens / max(t_end - phase_t0, 1e-9))
    if telemetry is not None:
        telemetry.registry.absorb_spans(spans)
        telemetry.registry.absorb_resilience(report.resilience)
        telemetry.events.run_end(
            steps=report.steps, start_step=report.start_step,
            preempted=report.preempted, remeshes=len(report.remeshes),
            tokens_per_sec=report.tokens_per_sec, wall_s=report.wall_time,
            post_remesh_tokens_per_sec=report.post_remesh_tokens_per_sec,
            metrics=telemetry.registry.snapshot())
        telemetry.heartbeat.beat(step=last_it + 1, phase="done")
    return report


def _apply_resilience(step_fn, resilience: Optional[ResilienceConfig],
                      fault_plan, ckpt, stats: ResilienceStats, *,
                      start: int = 0):
    """Compose the resilience layer around a trainer's step function:
    fault injection innermost (so the guard sees the faulted step — the two
    halves test each other), StepGuard outermost. ``fault_plan`` may come in
    as an object (tests) or via ``resilience.faults`` (CLI/config); fault
    step indices are post-resume call indices. ``start`` offsets the fault
    wrapper's dispatch counter — the elastic loop re-applies this to a step
    function REBUILT mid-run, and already-delivered faults must not
    re-fire (the StepGuard starts fresh either way: its EMA detector must
    re-learn the new topology's update norms)."""
    if fault_plan is None and resilience is not None and resilience.faults:
        fault_plan = resilience.fault_plan()
    if fault_plan:
        step_fn = fault_plan.wrap_step(step_fn, start=start)
    if resilience is not None and resilience.guard:
        from ..resilience.guard import StepGuard
        step_fn = StepGuard(
            step_fn, ckpt=ckpt, stats=stats,
            max_consecutive_bad=resilience.max_consecutive_bad,
            ema_decay=resilience.ema_decay,
            anomaly_factor=resilience.anomaly_factor,
            ema_warmup=resilience.ema_warmup)
    return step_fn


def train_llm_dp(model_cfg: Optional[LlamaConfig] = None,
                 train_cfg: Optional[TrainConfig] = None, *,
                 mesh=None,
                 tokenizer=None,
                 aggregation: str = "gradient",
                 log_every: int = 100,
                 log_fn: Callable[[str], None] = print,
                 warmup_steps_excluded: int = 2,
                 checkpoint_dir: Optional[str] = None,
                 checkpoint_every: int = 1000,
                 loss_sink: Optional[Callable[[int, float], None]] = None,
                 sink_every: int = 10,
                 resilience: Optional[ResilienceConfig] = None,
                 fault_plan=None,
                 telemetry=None,
                 on_checkpoint=None,
                 scale_hook=None) -> LLMTrainReport:
    """Run DP tiny-Llama training; returns losses and throughput.

    ``aggregation``: "gradient" (allreduce grads — intro_DP_GA), "weight"
    (allreduce weights post-step — intro_DP_WA's intended semantics), or
    "zero1" (ZeRO-1 sharded weight update, dp.make_zero1_step: gradients
    reduce-scattered, Adam applied to each replica's 1/N slice with
    optimizer state sharded from init, fresh params all-gathered — N× less
    optimizer memory and update FLOPs at allreduce-parity wire bytes).

    ``train_cfg.steps_per_dispatch`` = K > 1 turns on the fused multi-step
    driver (gradient/zero1 aggregation, fp32 wire only): K steps scanned in
    one compiled, donated dispatch over a [K, B, T] batch window, host work
    quantized to chunk edges — semantics spelled out in ``_run_loop``.

    ``train_cfg.overlap_microbatches`` = M >= 1 routes gradient sync
    through the overlapped ring driver (parallel/compress.py
    ``make_overlap_step`` / ``make_overlap_multi_step``): the batch splits
    into M microbatches whose grad computes overlap the previous
    microbatch's ppermute-pipelined ring reduce-scatter, with in-flight
    chunks in the ``wire`` format — the one path where wire compression
    composes with zero1 AND steps_per_dispatch. int8 EF residuals live in
    the state tree, so checkpoints/preemption carry them exactly. Replaces
    ``accum_steps`` (same batch axis); ``numerics_every``, the fused
    ``injit_guard`` and ``resilience.elastic`` all compose (elastic
    reshards the EF residual trees across re-meshes).

    ``train_cfg.dcn`` = D > 1 makes the DP world HIERARCHICAL: D ICI
    islands of ``data`` replicas bridged by DCN (hier_data_mesh), with
    gradient sync through the TWO-LEVEL ring driver (requires
    ``overlap_microbatches`` >= 1) — full-precision reduce-scatter within
    each island (``wire``: fp32/bf16), the exchange across the DCN axis
    in ``wire_dcn`` (int8+EF is the headline: ~1/S of the vector crosses
    DCN, at one byte/element), then the intra-island gather. The
    telemetry comm profile attributes bytes per mesh axis, so the DCN
    budget is first-class (manifest ``comm.axes``, gated in
    experiments/comm_wire_smoke.py).

    ``loss_sink(it, loss)`` fires every ``sink_every`` iterations with the
    host-synced loss — for incremental result recording that survives a
    killed run (each call forces a device sync; use only where the step
    time dwarfs it, e.g. the oversubscribed virtual-CPU mesh).

    ``checkpoint_dir`` enables orbax checkpoint/resume (the persistence layer
    the reference lacks, SURVEY.md §5.4): the newest VALID step in the
    directory is restored into the mesh layout before training (a corrupt
    latest step falls back — checkpoint.py), a checkpoint is written every
    ``checkpoint_every`` steps and at the end, and already-completed
    iterations are skipped — re-running the same call after an interruption
    continues where it stopped. SIGTERM mid-loop force-saves a resumable
    checkpoint and returns with ``report.preempted=True``.

    ``resilience`` (config.ResilienceConfig) wraps the step in a StepGuard
    (skip non-finite steps, EMA spike detection, rollback after K
    consecutive bad steps) and carries the checkpoint-IO retry budget.
    ``fault_plan`` (resilience.FaultPlan) injects deterministic faults for
    tests/chaos runs; counters come back in ``report.resilience``.

    ``resilience.elastic=True`` (gradient/zero1 only) survives replica
    loss: a ``device_loss`` fault (or any ``ReplicaLossError``) at
    dispatch k drains the loop at the chunk edge, re-meshes onto the
    surviving devices, reshards params + ZeRO-1 optimizer state to the
    new world size (host-RAM mirror fast path / checkpoint slow path —
    resilience/elastic.py), re-splits the stream and resumes; recovery
    records land in ``report.remeshes`` and the telemetry ``remesh``
    event. With zero faults the elastic loop's losses are bitwise the
    non-elastic path's. Elasticity is bidirectional: a ``device_return``
    fault (or any ``ReplicaReturnSignal``) grows the mesh back onto
    returned devices through the same machinery, with the same bitwise
    bar; with ``overlap_microbatches >= 1`` the compressed-wire ring
    driver composes too (EF residuals reshard alongside the moments).

    ``scale_hook(it, world)`` (requires ``resilience.elastic=True``) is
    the autoscaler's capacity-change seam: polled at every chunk edge
    with the just-drained stream position and current data world; a
    non-None return is the TARGET world, and the loop re-meshes to it via
    ``ElasticController.resize`` — snapshot at the edge, reshard, zero
    steps lost — before continuing (resilience/autoscale.py drives this
    from serving-side SLO pressure).

    ``telemetry`` (telemetry.Telemetry) opens the run's observability
    surface: a manifest event with the step's static comm profile, per-step
    records + heartbeat from the loop, fault deltas, and a run_end metrics
    snapshot — render with ``python -m experiments.obs_report <dir>``.

    ``on_checkpoint(step, state)`` is the checkpoint PUBLICATION hook —
    the train→deploy seam (serving/deploy.py): called after every
    successful periodic and final save (requires ``checkpoint_dir``), so
    a ``CheckpointPublisher`` can stream params-only snapshots to a
    serving fleet that hot-swaps them live. Guarded: a broken hook is
    logged and skipped, never fatal.
    """
    if model_cfg is not None and not isinstance(model_cfg, LlamaConfig):
        raise NotImplementedError(
            "the trainer takes LlamaConfig models only: a loss, a backward "
            "pass and expert-parallel exchange for a described model "
            f"({type(model_cfg).__name__}: latent attention, routed experts) "
            "are not built (ROADMAP.md)")
    tok = tokenizer or load_tokenizer()
    model_cfg = (model_cfg or LlamaConfig()).replace(vocab_size=tok.vocab_size)
    train_cfg = train_cfg or TrainConfig()
    if mesh is None:
        if train_cfg.dcn > 1:
            # Hierarchical DP: dcn ICI islands of ``data`` replicas,
            # bridged by DCN (parallel/distributed.py:hier_data_mesh).
            from ..parallel.distributed import hier_data_mesh
            mesh = hier_data_mesh(train_cfg.dcn, train_cfg.data)
        else:
            mesh = make_mesh({"data": train_cfg.data})
    n_dcn = mesh.shape.get("dcn", 1)
    # The TOTAL data-parallel world — stream splits, batch shapes and
    # token accounting all run at dcn·data width on a hierarchical mesh.
    n_data = mesh.shape.get("data", 1) * n_dcn
    if train_cfg.wire_dcn and "dcn" not in mesh.shape:
        raise ValueError(
            "wire_dcn selects the DCN tier of a hierarchical mesh; set "
            "TrainConfig.dcn > 1 (or pass a hier_data_mesh)")
    if train_cfg.dcn > 1 and "dcn" not in mesh.shape:
        # Same bar as the wire_dcn check above: silently training the
        # flat ring while the config ASKS for islands would fake a
        # hierarchical measurement (no comm.axes, no DCN tier).
        raise ValueError(
            f"TrainConfig.dcn={train_cfg.dcn} but the supplied mesh has "
            "no 'dcn' axis — pass a hier_data_mesh (or drop the explicit "
            "mesh and let the trainer build one)")
    hier = n_dcn > 1 or (bool(train_cfg.wire_dcn) and "dcn" in mesh.shape)

    params = llama.init_llama(jax.random.key(train_cfg.seed), model_cfg)
    optimizer = _make_trainer_optimizer(train_cfg)

    def loss_fn(p, batch):
        # Fused head+CE: never materializes the [B, T, V] logits (the step's
        # dominant HBM tensor at real vocab sizes). Equivalent math to
        # causal_lm_loss(llama.forward(...)) — asserted in tests/test_core.py.
        return llama.forward_loss(p, batch, model_cfg)

    spd = train_cfg.steps_per_dispatch
    if spd < 1:
        raise ValueError(f"steps_per_dispatch must be >= 1 (got {spd})")
    ovl = train_cfg.overlap_microbatches
    if ovl < 0:
        raise ValueError(f"overlap_microbatches must be >= 0 (got {ovl})")
    cb = train_cfg.comm_buckets
    if cb < 1:
        raise ValueError(f"comm_buckets must be >= 1 (got {cb})")
    if cb > 1 and ovl == 0:
        raise ValueError(
            "comm_buckets > 1 is a property of the overlap/ring driver "
            "(the bucketed backward splits each microbatch's ring) — set "
            f"overlap_microbatches >= 1 (got comm_buckets={cb} with "
            "overlap_microbatches=0)")
    elastic = bool(resilience is not None and resilience.elastic)
    if hier and ovl == 0:
        raise ValueError(
            "a hierarchical mesh (TrainConfig.dcn > 1 / wire_dcn) routes "
            "gradient sync through the two-level ring driver: set "
            "overlap_microbatches >= 1")
    numerics = None
    if train_cfg.numerics_every > 0:
        # In-jit run-health numerics (telemetry/introspect.py): supported
        # wherever a shared step body computes it — gradient/zero1 on the
        # fp32 wire, AND the overlap/ring drivers at any wire format and
        # topology (the summary rides the step outputs; the ring schedule
        # is untouched). Hard errors elsewhere, not silent no-ops: a
        # chaos run that THINKS it is instrumented but isn't would
        # produce attribution-free bundles.
        if aggregation not in ("gradient", "zero1"):
            raise ValueError("numerics_every requires gradient or zero1 "
                             f"aggregation (got {aggregation!r})")
        if ovl == 0 and train_cfg.wire != "fp32":
            raise ValueError(
                "numerics_every requires wire='fp32' on the legacy "
                "per-step compressed paths (they own their collective "
                "schedules) — overlap_microbatches >= 1 is the composing "
                "path")
        if elastic:
            raise ValueError("numerics_every does not compose with "
                             "elastic mode yet")
        if ovl:
            # Overlap/ring drivers: local gradients differ per shard in
            # BOTH aggregations, so the summarizer psum-agrees grad stats
            # over every data axis of the (possibly hierarchical) mesh.
            psum_axis = ("dcn", "data") if hier else "data"
        else:
            psum_axis = "data" if aggregation == "zero1" else None
        numerics = introspect.make_summarizer(params, psum_axis=psum_axis)
    injit_guard = bool(resilience is not None and resilience.injit_guard)
    if injit_guard:
        # The fused in-jit skip (parallel/{dp,compress}.py
        # guard_nonfinite): select-back without leaving jit, the
        # non-advancing step counter counted into
        # ResilienceStats.skipped_steps at the end-of-run sync.
        if resilience.guard:
            raise ValueError(
                "injit_guard and guard are mutually exclusive skip "
                "mechanisms (the host StepGuard would double-count the "
                "fused skip); set ResilienceConfig(guard=False) to use "
                "the in-jit guard")
        if elastic:
            raise ValueError("injit_guard does not compose with elastic "
                             "mode (the remesh path rebuilds its own "
                             "steps)")
        if aggregation not in ("gradient", "zero1"):
            raise ValueError("injit_guard requires gradient or zero1 "
                             f"aggregation (got {aggregation!r})")
        if ovl == 0 and train_cfg.wire != "fp32":
            raise ValueError(
                "injit_guard is not fused into the legacy per-step "
                "compressed paths — overlap_microbatches >= 1 is the "
                "composing path")
    if scale_hook is not None and not elastic:
        raise ValueError("scale_hook requires resilience.elastic=True — "
                         "capacity changes ride the elastic re-mesh "
                         "machinery")
    if elastic:
        # Elastic DP (resilience/elastic.py): the loop drives the [K, B, T]
        # window step (K = steps_per_dispatch, 1 included) so replica-loss
        # drain/recovery quantizes to chunk edges. Gradient/zero1 only —
        # the weight-aggregation step owns a collective schedule nobody
        # has taught to re-mesh. Compressed wire composes through the
        # overlap/ring driver: its EF residual trees reshard N→M with the
        # ZeRO-1 moments (parallel/dp.py reshard_state's ring-residual
        # pre-pass), so elastic × int8_ef is a supported pairing.
        if aggregation not in ("gradient", "zero1"):
            raise ValueError("elastic mode supports gradient and zero1 "
                             f"aggregation only (got {aggregation!r})")
        if train_cfg.wire != "fp32" and ovl == 0:
            raise ValueError(
                f"elastic=True composes with wire={train_cfg.wire!r} only "
                "through the overlap/ring driver, whose EF residual trees "
                "(OverlapEFState.ring_residual/gather_residual) the remesh "
                "path reshards N→M alongside the ZeRO-1 moments — the "
                "legacy per-step compressed paths own collective schedules "
                "nobody re-meshes. Set overlap_microbatches >= 1, or use "
                "wire='fp32'")
        if any(s > 1 for a, s in mesh.shape.items() if a != "data"):
            raise ValueError("elastic mode supports data-axis-only meshes "
                             f"(got {dict(mesh.shape)})")
        # Pin the init params to host memory (see the PP elastic path):
        # device_put can alias a compatibly-placed leaf into the first
        # build's donated state, deleting the buffer a rebuild needs.
        params = jax.tree.map(np.asarray, params)

        def _build_elastic(m):
            """(template_state, raw window step, window shard fn) on an
            arbitrary data mesh — initial build AND post-remesh rebuild go
            through here, so the two cannot drift."""
            if ovl >= 1:
                from ..parallel import compress
                st, fn = compress.make_overlap_multi_step(
                    loss_fn, optimizer, m, params, microbatches=ovl,
                    wire=train_cfg.wire, aggregation=aggregation,
                    comm_buckets=cb)
            elif aggregation == "zero1":
                st, fn = dp.make_zero1_multi_step(loss_fn, optimizer, m,
                                                  params)
            else:
                fn = dp.make_multi_step(loss_fn, optimizer, m,
                                        accum_steps=train_cfg.accum_steps)
                st = dp.replicate(m, dp.init_state(params, optimizer))
            # Each (re)build gets its own CompileWatch: the post-remesh
            # recompile is then a visible ``compile`` event in the stream,
            # world-size-tagged — no retrace budget (tail chunks + remesh
            # recompiles are legitimate).
            fn = introspect.watch(
                fn, name=f"train/dp-{aggregation}-elastic"
                         + (f"-ring{train_cfg.wire}-m{ovl}" if ovl else "")
                         + (f"-b{cb}" if cb > 1 else "")
                         + f"-w{m.shape['data']}",
                max_caches=None,
                events=(telemetry.events if telemetry is not None
                        else None),
                meta={"steps_per_dispatch": spd},
                meta_fn=lambda st, w: {"steps_per_dispatch":
                                       int(w.shape[0])})
            return st, fn, (lambda w, m=m: dp.shard_batch_window(m, w))
    state = None
    if ovl >= 1:
        # Overlapped+compressed gradient sync (parallel/compress.py ring
        # driver): the one path where wire ∈ {fp32, bf16, int8_ef}
        # composes with aggregation ∈ {gradient, zero1} AND
        # steps_per_dispatch. Microbatching replaces accum_steps (both
        # split the same batch axis); hard errors, not asserts.
        if aggregation not in ("gradient", "zero1"):
            raise ValueError("overlap_microbatches supports gradient and "
                             f"zero1 aggregation only (got {aggregation!r})")
        if train_cfg.accum_steps != 1:
            raise ValueError("overlap_microbatches replaces accum_steps "
                             "(both split the local batch axis); set "
                             "accum_steps=1")
        from ..parallel import compress
        # Per-axis wire on the hierarchical mesh: the ICI tier rides
        # ``wire``, the scarce DCN tier ``wire_dcn`` (default fp32).
        wire_arg = ({"ici": train_cfg.wire,
                     "dcn": train_cfg.wire_dcn or "fp32"}
                    if hier else train_cfg.wire)
        if elastic:
            state, step_fn, window_shard = _build_elastic(mesh)
        elif spd > 1:
            state, step_fn = compress.make_overlap_multi_step(
                loss_fn, optimizer, mesh, params, microbatches=ovl,
                wire=wire_arg, aggregation=aggregation, comm_buckets=cb,
                guard_nonfinite=injit_guard, numerics=numerics)
        else:
            state, step_fn = compress.make_overlap_step(
                loss_fn, optimizer, mesh, params, microbatches=ovl,
                wire=wire_arg, aggregation=aggregation, comm_buckets=cb,
                guard_nonfinite=injit_guard, numerics=numerics)
    elif train_cfg.wire != "fp32":
        # Compressed gradient allreduce (parallel/compress.py) — gradient
        # aggregation only, and accumulation stays at 1 (the compressed
        # steps own their collective schedule). Hard errors, not asserts:
        # a stripped assert (python -O) would silently run the wrong
        # aggregation algorithm.
        if aggregation != "gradient" or train_cfg.accum_steps != 1 \
                or spd != 1:
            raise ValueError(
                "wire compression requires gradient aggregation without "
                "accumulation or multi-step dispatch (got "
                f"aggregation={aggregation!r}, "
                f"accum_steps={train_cfg.accum_steps}, "
                f"steps_per_dispatch={spd}) — overlap_microbatches >= 1 "
                "is the composing path")
        from ..parallel import compress
        if train_cfg.wire == "bf16":
            step_fn = compress.make_bf16_grad_step(loss_fn, optimizer, mesh)
        elif train_cfg.wire == "int8_ef":
            state = compress.init_ef_state(mesh, params, optimizer)
            step_fn = compress.make_int8_ef_grad_step(loss_fn, optimizer,
                                                      mesh)
        else:
            raise ValueError(f"unknown wire format {train_cfg.wire!r}")
    elif aggregation == "zero1":
        if train_cfg.accum_steps != 1:
            raise ValueError("accum_steps composes with gradient "
                             "aggregation only (zero1 scatters the raw "
                             "local gradient)")
        if elastic:
            state, step_fn, window_shard = _build_elastic(mesh)
        elif spd > 1:
            state, step_fn = dp.make_zero1_multi_step(
                loss_fn, optimizer, mesh, params,
                guard_nonfinite=injit_guard, numerics=numerics)
        else:
            state, step_fn = dp.make_zero1_step(
                loss_fn, optimizer, mesh, params,
                guard_nonfinite=injit_guard, numerics=numerics)
    elif aggregation == "gradient":
        if elastic:
            state, step_fn, window_shard = _build_elastic(mesh)
        elif spd > 1:
            step_fn = dp.make_multi_step(
                loss_fn, optimizer, mesh, accum_steps=train_cfg.accum_steps,
                guard_nonfinite=injit_guard, numerics=numerics)
        else:
            step_fn = dp.make_grad_aggregation_step(
                loss_fn, optimizer, mesh, accum_steps=train_cfg.accum_steps,
                guard_nonfinite=injit_guard, numerics=numerics)
    elif aggregation == "weight":
        if train_cfg.accum_steps != 1:
            raise ValueError("accum_steps needs gradient aggregation")
        if spd != 1:
            raise ValueError("steps_per_dispatch > 1 supports gradient and "
                             "zero1 aggregation only")
        step_fn = dp.make_weight_aggregation_step(loss_fn, optimizer, mesh)
    else:
        raise ValueError(f"unknown aggregation {aggregation!r}: expected "
                         "'gradient', 'weight' or 'zero1'")
    if state is None:
        state = dp.replicate(mesh, dp.init_state(params, optimizer))

    if not elastic:
        # Compile/retrace observability (introspect.CompileWatch): every
        # XLA compilation of the hot-path step becomes a ``compile`` event
        # (wall seconds, HLO flops/bytes for attainment, cache-hit vs
        # retrace). Per-step mode promises ONE compiled program
        # (max_caches=1 — growth past it is a retrace bug); chunked mode
        # legitimately compiles a tail-chunk shape, so no budget there.
        # The elastic path wraps inside _build_elastic instead (each
        # re-mesh rebuild gets its own watch). Transparent to
        # measure_comm/eval_shape — attribute access delegates.
        step_fn = introspect.watch(
            step_fn,
            name=f"train/dp-{aggregation}"
                 + (f"-k{spd}" if spd > 1 else "")
                 + ((f"-hier{n_dcn}x{mesh.shape['data']}"
                     f"-{train_cfg.wire}/{train_cfg.wire_dcn or 'fp32'}"
                     f"-m{ovl}") if hier else
                    (f"-ring{train_cfg.wire}-m{ovl}" if ovl else ""))
                 + (f"-b{cb}" if cb > 1 else ""),
            max_caches=(1 if spd == 1 else None),
            events=(telemetry.events if telemetry is not None else None),
            # Chunked mode stamps each compile event with the COMPILING
            # call's actual window size — a tail chunk's smaller program
            # must not be normalized as a full-K one (slo_monitor's
            # per-step MFU arithmetic divides flops by this).
            meta={"steps_per_dispatch": spd},
            meta_fn=(None if spd == 1 else
                     (lambda st, w: {"steps_per_dispatch":
                                     int(w.shape[0])})))
    compile_watch = step_fn if not elastic else None

    stats = ResilienceStats()
    ckpt, state, start_step, done = _setup_checkpoint(
        checkpoint_dir, state, train_cfg.iters, log_fn,
        resilience=resilience, stats=stats)
    if done:
        return LLMTrainReport(resilience=stats)
    # Memory observability (telemetry/memory.py): the preflight fit
    # estimate lands in the manifest (obs_report tables it against the
    # measured compile-event footprint), and its per-device state figures
    # seed the live meter that samples at every step-event cadence point.
    # Both are guarded — a backend that can't account bytes degrades to
    # None/empty, never blocks training.
    pre = memory_meter = None
    if telemetry is not None:
        from ..telemetry import memory as memlib
        pre = memlib.preflight(model_cfg, train_cfg, mesh=mesh,
                               aggregation=aggregation)
        memory_meter = memlib.MemoryMeter(telemetry.events, source="train")
        if pre is not None:
            memory_meter.note(params_bytes=pre["params_bytes"],
                              opt_state_bytes=pre["opt_state_bytes"],
                              residual_bytes=pre["residual_bytes"] or None,
                              window_bytes=pre["window_bytes"] or None)
    comm_profile = _emit_manifest(
        telemetry, trainer="dp", model_cfg=model_cfg, train_cfg=train_cfg,
        mesh=mesh, start_step=start_step, step_fn=step_fn, state=state,
        n_data=n_data, steps_per_dispatch=spd, windowed=elastic,
        overlap_microbatches=max(1, ovl), preflight=pre)
    if fault_plan is None and resilience is not None and resilience.faults:
        fault_plan = resilience.fault_plan()   # resolve ONCE: the elastic
        #   rebuild must re-wrap the same schedule, not a fresh counter's

    def _make_batches(n):
        # Disjoint stream windows per data shard — the reference's
        # skip=rank*5000. Recovery re-splits at the new width through
        # this same constructor, so the post-remesh data order is exactly
        # a fresh n-replica run's.
        return sharded_batches(tok, train_cfg.batch_size, train_cfg.seq_len,
                               n, shard_skip=5000, seed=train_cfg.seed)

    if elastic:
        from ..resilience.elastic import ElasticController

        def _rewrap(fn, start=0):
            return _apply_resilience(fn, resilience, fault_plan, ckpt,
                                     stats, start=start)

        controller = ElasticController(
            mesh, build=_build_elastic, rewrap=_rewrap,
            make_batches=_make_batches, ckpt=ckpt,
            mirror_every=resilience.mirror_every, stats=stats,
            telemetry=telemetry, log_fn=log_fn)
        return _run_elastic_loop(
            controller, _rewrap(step_fn), state, _make_batches(n_data),
            train_cfg, n_data=n_data, start_step=start_step, ckpt=ckpt,
            checkpoint_every=checkpoint_every, loss_sink=loss_sink,
            sink_every=sink_every, log_every=log_every, log_fn=log_fn,
            warmup_steps_excluded=warmup_steps_excluded, stats=stats,
            telemetry=telemetry, steps_per_dispatch=spd,
            window_shard_fn=window_shard, on_checkpoint=on_checkpoint,
            scale_hook=scale_hook, memory_meter=memory_meter)
    step_fn = _apply_resilience(step_fn, resilience, fault_plan, ckpt, stats)

    batches = _make_batches(n_data)
    return _run_loop(step_fn, state, batches, train_cfg,
                     lambda b: dp.shard_batch(mesh, b), n_data=n_data,
                     start_step=start_step, ckpt=ckpt,
                     checkpoint_every=checkpoint_every, loss_sink=loss_sink,
                     sink_every=sink_every, log_every=log_every,
                     log_fn=log_fn, comm_profile=comm_profile,
                     warmup_steps_excluded=warmup_steps_excluded,
                     stats=stats, telemetry=telemetry,
                     steps_per_dispatch=spd,
                     window_shard_fn=lambda w: dp.shard_batch_window(mesh, w),
                     numerics=numerics,
                     numerics_every=train_cfg.numerics_every,
                     compile_watch=compile_watch,
                     injit_guard=injit_guard,
                     on_checkpoint=on_checkpoint,
                     memory_meter=memory_meter)


def train_llm_pp(model_cfg: Optional[LlamaConfig] = None,
                 train_cfg: Optional[TrainConfig] = None, *,
                 mesh=None,
                 tokenizer=None,
                 schedule: str = "gpipe",
                 aggregation: str = "gradient",
                 log_every: int = 100,
                 log_fn: Callable[[str], None] = print,
                 warmup_steps_excluded: int = 2,
                 checkpoint_dir: Optional[str] = None,
                 checkpoint_every: int = 1000,
                 loss_sink: Optional[Callable[[int, float], None]] = None,
                 sink_every: int = 10,
                 resilience: Optional[ResilienceConfig] = None,
                 fault_plan=None,
                 scale_hook=None,
                 on_checkpoint=None,
                 telemetry=None) -> LLMTrainReport:
    """Pipeline(-x-data)-parallel tiny-Llama training; returns losses and
    throughput.

    Capability target: the reference's 3-stage microbatched pipeline run
    (lab/hw01/homework 1 b/homework_1_b1.py, committed log out_b1_2.txt:
    loss 10.517 -> ~6.0 over 5000 iters) and the 2-pipeline x 3-stage DPxPP
    topology (homework_1_b2.py, out_b2_*.txt). ``train_cfg.stage``/
    ``train_cfg.data``/``train_cfg.microbatches`` pick the topology; each
    data shard reads a disjoint stream window (shard_skip=5000), matching
    the reference's per-pipeline data offset.

    The DP fast-path levers now compose here too (the PR 14 column):

    - ``train_cfg.steps_per_dispatch`` = K > 1 drives the fused K-step
      scan driver (pp.make_pipeline_multi_step — any schedule) through the
      same chunked ``_run_loop`` mode as the DP trainer: one compiled,
      donated dispatch per K steps, host work (checkpoint / StepGuard /
      sink / telemetry / numerics sampling) quantized to chunk edges,
      losses bitwise-identical to K=1 (tests/test_pp.py), misaligned
      resume realigning with one smaller first chunk.
    - ``aggregation="zero1"`` + ``train_cfg.overlap_microbatches`` = M ≥ 1
      routes the DP×PP data-axis sync of the cross-stage-reduced gradient
      through the compressed/overlapped ring
      (pp.make_pipeline_overlap_*): ZeRO-1 moments sharded over
      ``(data, stage)`` ride the scan carry, ``train_cfg.wire`` selects
      the in-flight ring format (fp32/bf16/int8_ef — EF residuals in the
      checkpointed state, preempt/resume bitwise).
    - ``train_cfg.numerics_every`` emits stage-stacked in-jit numerics
      (pp.make_pp_numerics — block groups stage-qualified, losses bitwise
      on/off).

    Elastic mode (``resilience.elastic=True``) now composes here: a
    ``device_loss`` on the DP×PP mesh drains at the chunk edge and
    re-meshes — dropping the victims' data rows whole when a complete
    row survives (pure reshard at the same stage count), else
    RE-PARTITIONING layers over the survivors at the largest stage count
    dividing ``n_layers`` (``pp.repartition_stage_state`` rewrites the
    ``(data, stage)`` ZeRO-1/EF stacks through topology-invariant
    coordinate ids). ``device_return`` grows back toward the original
    ``(D, S)`` factorization via pool-order rejoin. Named non-composing
    combinations: ``schedule="interleaved"`` (the chunk-major layer
    order breaks the blocked stage slices a re-partition re-slices) and
    ``numerics_every`` (as on the DP trainer).

    Still DP-trainer-only (hard errors): hierarchical DCN tiers
    (``dcn``/``wire_dcn`` — the PP mesh has no two-level data tier),
    the fused in-jit guard, and ``accum_steps`` (the pipeline schedule
    owns its microbatching).

    ``checkpoint_dir`` enables orbax checkpoint/resume with stream replay,
    the same contract as train_llm_dp: restore the latest step (sharding-
    preserving — stage-sharded params land back on their stages), skip
    already-completed iterations while still consuming the token stream so
    data order is preserved, save every ``checkpoint_every`` steps and at
    the end. Both trainers share one loop implementation (_run_loop), so
    timing/throughput/resume semantics cannot drift between them.
    """
    tok = tokenizer or load_tokenizer()
    model_cfg = (model_cfg or LlamaConfig()).replace(vocab_size=tok.vocab_size)
    train_cfg = train_cfg or TrainConfig()
    spd = train_cfg.steps_per_dispatch
    ovl = train_cfg.overlap_microbatches
    if spd < 1:
        raise ValueError(f"steps_per_dispatch must be >= 1 (got {spd})")
    if ovl < 0:
        raise ValueError(f"overlap_microbatches must be >= 0 (got {ovl})")
    cb = train_cfg.comm_buckets
    if cb < 1:
        raise ValueError(f"comm_buckets must be >= 1 (got {cb})")
    if cb > 1 and ovl == 0:
        raise ValueError(
            "comm_buckets > 1 is a property of the overlap/ring driver "
            "(the bucketed backward splits each microbatch's ring) — set "
            f"overlap_microbatches >= 1 (got comm_buckets={cb} with "
            "overlap_microbatches=0)")
    if train_cfg.dcn != 1 or train_cfg.wire_dcn:
        raise ValueError("hierarchical DP (TrainConfig.dcn / wire_dcn) is "
                         "DP-trainer-only; the pipeline mesh has no "
                         "two-level data tier")
    if train_cfg.accum_steps != 1:
        raise ValueError("accum_steps (DP gradient accumulation) is "
                         "DP-trainer-only: the pipeline schedule owns its "
                         "microbatching — raise TrainConfig.microbatches "
                         "instead")
    if aggregation not in ("gradient", "zero1"):
        raise ValueError(f"unknown aggregation {aggregation!r}: the PP "
                         "trainer supports 'gradient' and 'zero1'")
    if train_cfg.wire != "fp32" and ovl == 0:
        raise ValueError(
            "wire compression on the PP trainer routes through the DP×PP "
            "ring driver: set overlap_microbatches >= 1 "
            f"(got wire={train_cfg.wire!r} with overlap_microbatches=0)")
    if aggregation == "zero1" and ovl == 0:
        raise ValueError(
            "PP zero1 routes the data-axis sync through the ring driver: "
            "set overlap_microbatches >= 1")
    elastic = bool(resilience is not None and resilience.elastic)
    if elastic and schedule == "interleaved":
        raise ValueError(
            "elastic mode does not compose with schedule='interleaved': a "
            "stage re-partition re-slices the blocked [n_layers/S] stage "
            "shards, and the interleaved chunk-major layer order breaks "
            "that contiguity — use schedule='gpipe' or '1f1b'")
    if elastic and train_cfg.numerics_every > 0:
        raise ValueError("numerics_every does not compose with elastic "
                         "mode yet")
    if scale_hook is not None and not elastic:
        raise ValueError("scale_hook requires resilience.elastic=True — "
                         "capacity changes ride the elastic re-mesh "
                         "machinery")
    if resilience is not None and resilience.injit_guard:
        raise ValueError("injit_guard is not fused into the pipeline step "
                         "bodies — use the host StepGuard "
                         "(ResilienceConfig.guard), which works at "
                         "dispatch granularity under steps_per_dispatch")
    mesh = mesh or make_mesh({"data": train_cfg.data,
                              "stage": train_cfg.stage})
    n_data = mesh.shape.get("data", 1)

    params = llama.init_llama(jax.random.key(train_cfg.seed), model_cfg)
    optimizer = _make_trainer_optimizer(train_cfg)
    if schedule == "interleaved":
        params = pp.interleave_params(params, mesh.shape["stage"],
                                      n_chunks=2)
    numerics = None
    if train_cfg.numerics_every > 0:
        # Stage-stacked in-jit numerics (pp.make_pp_numerics): block
        # groups come back per (stage, local layer); the ring/zero1 path
        # psum-agrees grad stats over ``data`` (local gradients differ
        # per data shard there — the compress.py rule).
        numerics = pp.make_pp_numerics(params, mesh, psum_data=ovl >= 1)

    window_shard = None
    if elastic:
        # Pin the init params to host memory: ``jax.device_put`` may
        # alias (not copy) an already-compatibly-placed leaf into the
        # first build's state, and the donated dispatches then delete
        # that buffer — a post-remesh rebuild reading the closure would
        # hit "Array has been deleted". Host arrays are never donated.
        params = jax.tree.map(np.asarray, params)

        def _build_elastic(m):
            """(template_state, raw window step, window shard fn) on an
            arbitrary (data, stage) mesh — initial build AND post-remesh
            rebuild (including at a re-partitioned stage count) go through
            here, so the two cannot drift."""
            if ovl >= 1:
                st, fn = pp.make_pipeline_overlap_multi_step(
                    model_cfg, optimizer, m, params,
                    n_microbatches=train_cfg.microbatches,
                    schedule=schedule, aggregation=aggregation,
                    wire=train_cfg.wire, overlap_microbatches=ovl,
                    comm_buckets=cb)
            else:
                st = pp.init_state(m, params, optimizer)
                fn = pp.make_pipeline_multi_step(
                    model_cfg, optimizer, m,
                    n_microbatches=train_cfg.microbatches,
                    schedule=schedule)
            # Per-(re)build CompileWatch, tagged with the (D, S)
            # factorization: zero retraces per topology is the elastic
            # PP compile bar (tests/test_elastic.py), and the tag is what
            # makes a re-partition's recompile attributable in the event
            # stream.
            fn = introspect.watch(
                fn, name=f"train/pp-{schedule}-elastic"
                         + (f"-{aggregation}" if aggregation != "gradient"
                            else "")
                         + (f"-ring{train_cfg.wire}-m{ovl}" if ovl else "")
                         + (f"-b{cb}" if cb > 1 else "")
                         + f"-d{m.shape['data']}s{m.shape['stage']}",
                max_caches=None,
                events=(telemetry.events if telemetry is not None
                        else None),
                meta={"steps_per_dispatch": spd},
                meta_fn=lambda st, w: {"steps_per_dispatch":
                                       int(w.shape[0])})
            return st, fn, (lambda w, m=m: pp.shard_batch_window(m, w))

        state, step_fn, window_shard = _build_elastic(mesh)
    elif ovl >= 1:
        # DP×PP data-axis composition (pp.make_pipeline_overlap_*): the
        # cross-stage-reduced gradient's data sync rides the
        # compressed/overlapped ring; zero1 moments + EF residuals live
        # in the state tree (checkpoint/preempt carry them exactly).
        maker = (pp.make_pipeline_overlap_multi_step if spd > 1
                 else pp.make_pipeline_overlap_step)
        state, step_fn = maker(
            model_cfg, optimizer, mesh, params,
            n_microbatches=train_cfg.microbatches, schedule=schedule,
            aggregation=aggregation, wire=train_cfg.wire,
            overlap_microbatches=ovl, comm_buckets=cb, numerics=numerics)
    elif spd > 1:
        state = pp.init_state(mesh, params, optimizer)
        step_fn = pp.make_pipeline_multi_step(
            model_cfg, optimizer, mesh,
            n_microbatches=train_cfg.microbatches, schedule=schedule,
            numerics=numerics)
    else:
        state = pp.init_state(mesh, params, optimizer)
        step_fn = pp.make_pipeline_step(
            model_cfg, optimizer, mesh,
            n_microbatches=train_cfg.microbatches, schedule=schedule,
            numerics=numerics)
    # Compile/retrace accounting (introspect.CompileWatch), the DP
    # trainer's contract: per-step mode promises ONE compiled program;
    # chunked mode legitimately compiles a tail-chunk shape, so no budget
    # there — but every compile event is stamped with the COMPILING
    # call's actual window size, so per-step MFU normalization
    # (slo_monitor) stays honest for ragged tails. The elastic path wraps
    # inside _build_elastic instead (each re-mesh rebuild gets its own
    # topology-tagged watch).
    if not elastic:
        step_fn = introspect.watch(
            step_fn,
            name=f"train/pp-{schedule}"
                 + (f"-{aggregation}" if aggregation != "gradient" else "")
                 + (f"-k{spd}" if spd > 1 else "")
                 + (f"-ring{train_cfg.wire}-m{ovl}" if ovl else "")
                 + (f"-b{cb}" if cb > 1 else ""),
            max_caches=(1 if spd == 1 else None),
            events=(telemetry.events if telemetry is not None else None),
            meta={"steps_per_dispatch": spd},
            meta_fn=(None if spd == 1 else
                     (lambda st, w: {"steps_per_dispatch":
                                     int(w.shape[0])})))
    compile_watch = step_fn if not elastic else None

    stats = ResilienceStats()
    ckpt, state, start_step, done = _setup_checkpoint(
        checkpoint_dir, state, train_cfg.iters, log_fn,
        resilience=resilience, stats=stats)
    if done:
        return LLMTrainReport(resilience=stats)
    comm_profile = _emit_manifest(
        telemetry, trainer="pp", model_cfg=model_cfg, train_cfg=train_cfg,
        mesh=mesh, start_step=start_step, step_fn=step_fn, state=state,
        n_data=n_data, steps_per_dispatch=spd, windowed=elastic,
        overlap_microbatches=max(1, ovl))
    if fault_plan is None and resilience is not None and resilience.faults:
        fault_plan = resilience.fault_plan()   # resolve ONCE: the elastic
        #   rebuild must re-wrap the same schedule, not a fresh counter's

    def _make_batches(n):
        return sharded_batches(tok, train_cfg.batch_size, train_cfg.seq_len,
                               n, shard_skip=5000, seed=train_cfg.seed)

    if elastic:
        from ..resilience.elastic import ElasticController

        def _rewrap(fn, start=0):
            return _apply_resilience(fn, resilience, fault_plan, ckpt,
                                     stats, start=start)

        controller = ElasticController(
            mesh, build=_build_elastic, rewrap=_rewrap,
            make_batches=_make_batches, ckpt=ckpt,
            mirror_every=resilience.mirror_every,
            layer_divisor=model_cfg.n_layers, stats=stats,
            telemetry=telemetry, log_fn=log_fn)
        return _run_elastic_loop(
            controller, _rewrap(step_fn), state, _make_batches(n_data),
            train_cfg, n_data=n_data, start_step=start_step, ckpt=ckpt,
            checkpoint_every=checkpoint_every, loss_sink=loss_sink,
            sink_every=sink_every, log_every=log_every, log_fn=log_fn,
            warmup_steps_excluded=warmup_steps_excluded, stats=stats,
            telemetry=telemetry, steps_per_dispatch=spd,
            window_shard_fn=window_shard, on_checkpoint=on_checkpoint,
            scale_hook=scale_hook)
    step_fn = _apply_resilience(step_fn, resilience, fault_plan, ckpt, stats)

    batches = _make_batches(n_data)
    return _run_loop(step_fn, state, batches, train_cfg,
                     lambda b: pp.shard_batch(mesh, b), n_data=n_data,
                     start_step=start_step, ckpt=ckpt,
                     checkpoint_every=checkpoint_every, loss_sink=loss_sink,
                     sink_every=sink_every, log_every=log_every,
                     log_fn=log_fn, comm_profile=comm_profile,
                     warmup_steps_excluded=warmup_steps_excluded,
                     stats=stats, telemetry=telemetry,
                     steps_per_dispatch=spd,
                     window_shard_fn=lambda w: pp.shard_batch_window(mesh, w),
                     numerics=numerics,
                     numerics_every=train_cfg.numerics_every,
                     compile_watch=compile_watch,
                     on_checkpoint=on_checkpoint)


def train_llm_tp(model_cfg: Optional[LlamaConfig] = None,
                 train_cfg: Optional[TrainConfig] = None, *,
                 mesh=None,
                 tokenizer=None,
                 aggregation: str = "gradient",
                 log_every: int = 100,
                 log_fn: Callable[[str], None] = print,
                 warmup_steps_excluded: int = 2,
                 checkpoint_dir: Optional[str] = None,
                 checkpoint_every: int = 1000,
                 loss_sink: Optional[Callable[[int, float], None]] = None,
                 sink_every: int = 10,
                 resilience: Optional[ResilienceConfig] = None,
                 fault_plan=None,
                 scale_hook=None,
                 on_checkpoint=None,
                 telemetry=None) -> LLMTrainReport:
    """Tensor(-x-data)-parallel tiny-Llama training; returns losses and
    throughput.

    ``train_cfg.model`` picks the TP degree (Megatron column/row layout,
    parallel/tp.py) and ``train_cfg.data`` the data axis; each data shard
    reads a disjoint stream window (shard_skip=5000), exactly as the
    DP/PP trainers do. The fused-dispatch + overlapped/compressed sync
    column (the PR 14/18 levers) composes here:

    - ``train_cfg.psa`` relaxes the per-layer activation all-reduces off
      the critical path (TrainConfig.psa doc comment: "" bitwise legacy /
      "full" telemetry-visible baseline / "defer:L" / "int8_ef" with the
      per-layer EF residual tree riding the checkpointed state).
    - ``train_cfg.steps_per_dispatch`` = K > 1 drives the fused K-step
      scan driver (tp.make_tp_multi_step) through the same chunked
      ``_run_loop`` mode as the DP/PP trainers: one compiled, donated
      dispatch per K steps, host work quantized to chunk edges, losses
      bitwise-identical to K=1 (tests/test_tp.py).
    - ``aggregation="zero1"`` + ``train_cfg.overlap_microbatches`` = M ≥ 1
      routes the DATA-axis gradient sync through the compressed/overlapped
      ring on the DP×TP mesh (tp.make_tp_overlap_*): ZeRO-1 moments and
      EF residuals sharded ``(data, model)`` ride the scan carry,
      ``train_cfg.wire`` selects the ring format (fp32/bf16/int8_ef).
    - ``train_cfg.numerics_every`` emits in-jit numerics whose summaries
      are model-axis psum-agreed (tp.make_tp_numerics — every shard
      carries the same summary; losses bitwise on/off).

    Elastic mode (``resilience.elastic=True``) composes with the fused
    dispatch paths (``overlap_microbatches == 0``), INCLUDING
    ``psa="int8_ef"`` — the ROADMAP 7a lift: a data-axis re-mesh resizes
    the ``TPActState`` activation EF residual tree by the per-data-row
    rule (``dp._resize_act_residual``; surviving rows copy bitwise, new
    rows start at zero pending error), so preempt → remesh → resume under
    PSA is bitwise. The model axis itself never re-meshes (a model-axis
    device loss is unrecoverable — the Megatron layout is not
    layer-sliced), and the DP×TP ring drivers
    (``overlap_microbatches >= 1``) remain a named unsupported
    combination (their ``(data, model)`` ring stacks have no reshard
    rule yet).

    Still DP-trainer-only (hard errors): hierarchical DCN tiers, the
    fused in-jit guard, and ``accum_steps``.
    ``checkpoint_dir`` enables orbax checkpoint/resume with stream
    replay, the shared _run_loop contract — PSA EF residuals and ring
    residuals live in the state tree, so preempt/resume is bitwise.
    """
    tok = tokenizer or load_tokenizer()
    model_cfg = (model_cfg or LlamaConfig()).replace(vocab_size=tok.vocab_size)
    train_cfg = train_cfg or TrainConfig()
    spd = train_cfg.steps_per_dispatch
    ovl = train_cfg.overlap_microbatches
    psa = train_cfg.psa
    if spd < 1:
        raise ValueError(f"steps_per_dispatch must be >= 1 (got {spd})")
    if ovl < 0:
        raise ValueError(f"overlap_microbatches must be >= 0 (got {ovl})")
    cb = train_cfg.comm_buckets
    if cb < 1:
        raise ValueError(f"comm_buckets must be >= 1 (got {cb})")
    if cb > 1 and ovl == 0:
        raise ValueError(
            "comm_buckets > 1 is a property of the overlap/ring driver "
            "(the bucketed backward splits each microbatch's ring) — set "
            f"overlap_microbatches >= 1 (got comm_buckets={cb} with "
            "overlap_microbatches=0)")
    if train_cfg.dcn != 1 or train_cfg.wire_dcn:
        raise ValueError("hierarchical DP (TrainConfig.dcn / wire_dcn) is "
                         "DP-trainer-only; the TP mesh has no two-level "
                         "data tier")
    if train_cfg.accum_steps != 1:
        raise ValueError("accum_steps (DP gradient accumulation) is "
                         "DP-trainer-only; use overlap_microbatches on "
                         "the TP trainer's ring path")
    if aggregation not in ("gradient", "zero1"):
        raise ValueError(f"unknown aggregation {aggregation!r}: the TP "
                         "trainer supports 'gradient' and 'zero1'")
    if train_cfg.wire != "fp32" and ovl == 0:
        raise ValueError(
            "wire compression on the TP trainer routes through the DP×TP "
            "ring driver: set overlap_microbatches >= 1 "
            f"(got wire={train_cfg.wire!r} with overlap_microbatches=0)")
    if aggregation == "zero1" and ovl == 0:
        raise ValueError(
            "TP zero1 routes the data-axis sync through the ring driver: "
            "set overlap_microbatches >= 1")
    elastic = bool(resilience is not None and resilience.elastic)
    if elastic and ovl >= 1:
        raise ValueError(
            "elastic mode does not compose with the DP×TP ring driver "
            "(overlap_microbatches >= 1): its (data, model)-sharded ring "
            "stacks have no cross-topology reshard rule yet — set "
            "overlap_microbatches=0 (the fused dispatch paths, including "
            "psa='int8_ef', are elastic)")
    if elastic and train_cfg.numerics_every > 0:
        raise ValueError("numerics_every does not compose with elastic "
                         "mode yet")
    if scale_hook is not None and not elastic:
        raise ValueError("scale_hook requires resilience.elastic=True — "
                         "capacity changes ride the elastic re-mesh "
                         "machinery")
    if resilience is not None and resilience.injit_guard:
        raise ValueError("injit_guard is not fused into the TP step "
                         "bodies — use the host StepGuard "
                         "(ResilienceConfig.guard), which works at "
                         "dispatch granularity under steps_per_dispatch")
    mesh = mesh or make_mesh({"data": train_cfg.data,
                              "model": train_cfg.model})
    if mesh.shape.get("model", 1) < 2:
        raise ValueError("the TP trainer needs model >= 2 "
                         "(set TrainConfig.model); model=1 is the DP "
                         "trainer's mesh")
    n_data = mesh.shape.get("data", 1)

    params = llama.init_llama(jax.random.key(train_cfg.seed), model_cfg)
    optimizer = _make_trainer_optimizer(train_cfg)
    numerics = None
    if train_cfg.numerics_every > 0:
        # Model-axis psum-agreed in-jit numerics (tp.make_tp_numerics):
        # the ring/zero1 path additionally psum-agrees grad stats over
        # ``data`` (local gradients differ per data shard there — the
        # compress.py rule).
        numerics = tp.make_tp_numerics(params, mesh, psum_data=ovl >= 1)

    window_shard = None
    if elastic:
        # Pin the init params to host memory (see the PP elastic path):
        # device_put can alias a compatibly-placed leaf into the first
        # build's donated state, deleting the buffer a rebuild needs.
        params = jax.tree.map(np.asarray, params)

        def _build_elastic(m):
            """(template_state, raw window step, window shard fn) on an
            arbitrary (data, model) mesh — initial build AND post-remesh
            rebuild (data row-drop / grow; the model axis never re-meshes)
            go through here, so the two cannot drift."""
            st, fn = tp.make_tp_multi_step(
                model_cfg, optimizer, m, params, psa=psa,
                batch_shape=(train_cfg.batch_size, train_cfg.seq_len))
            # Per-(re)build CompileWatch, tagged with the (D, TP)
            # factorization: zero retraces per topology is the elastic
            # compile bar (tests/test_elastic.py).
            fn = introspect.watch(
                fn, name="train/tp-elastic"
                         + (f"-psa-{psa.replace(':', '')}" if psa else "")
                         + f"-d{m.shape['data']}x{m.shape['model']}",
                max_caches=None,
                events=(telemetry.events if telemetry is not None
                        else None),
                meta={"steps_per_dispatch": spd},
                meta_fn=lambda st, w: {"steps_per_dispatch":
                                       int(w.shape[0])})
            return st, fn, (lambda w, m=m: tp.shard_batch_window(m, w))

        state, step_fn, window_shard = _build_elastic(mesh)
    elif ovl >= 1:
        # DP×TP data-axis composition (tp.make_tp_overlap_*): the
        # model-psum-reduced gradient's data sync rides the compressed/
        # overlapped ring; zero1 moments + EF residuals sharded
        # (data, model) live in the state tree. psa="int8_ef" here is a
        # named unsupported combination (_tp_overlap_setup).
        maker = (tp.make_tp_overlap_multi_step if spd > 1
                 else tp.make_tp_overlap_step)
        state, step_fn = maker(
            model_cfg, optimizer, mesh, params,
            aggregation=aggregation, wire=train_cfg.wire,
            overlap_microbatches=ovl, psa=psa, comm_buckets=cb,
            numerics=numerics)
    else:
        maker = tp.make_tp_multi_step if spd > 1 else tp.make_tp_step
        state, step_fn = maker(
            model_cfg, optimizer, mesh, params, psa=psa,
            batch_shape=(train_cfg.batch_size, train_cfg.seq_len),
            numerics=numerics)
    # Compile/retrace accounting: the same contract as the DP/PP trainers
    # — per-step mode promises ONE compiled program; chunked mode stamps
    # every compile event with the COMPILING call's window size. The
    # elastic path wraps inside _build_elastic instead (each re-mesh
    # rebuild gets its own topology-tagged watch).
    if not elastic:
        step_fn = introspect.watch(
            step_fn,
            name="train/tp"
                 + (f"-psa-{psa.replace(':', '')}" if psa else "")
                 + (f"-{aggregation}" if aggregation != "gradient" else "")
                 + (f"-k{spd}" if spd > 1 else "")
                 + (f"-ring{train_cfg.wire}-m{ovl}" if ovl else "")
                 + (f"-b{cb}" if cb > 1 else ""),
            max_caches=(1 if spd == 1 else None),
            events=(telemetry.events if telemetry is not None else None),
            meta={"steps_per_dispatch": spd},
            meta_fn=(None if spd == 1 else
                     (lambda st, w: {"steps_per_dispatch":
                                     int(w.shape[0])})))
    compile_watch = step_fn if not elastic else None

    stats = ResilienceStats()
    ckpt, state, start_step, done = _setup_checkpoint(
        checkpoint_dir, state, train_cfg.iters, log_fn,
        resilience=resilience, stats=stats)
    if done:
        return LLMTrainReport(resilience=stats)
    comm_profile = _emit_manifest(
        telemetry, trainer="tp", model_cfg=model_cfg, train_cfg=train_cfg,
        mesh=mesh, start_step=start_step, step_fn=step_fn, state=state,
        n_data=n_data, steps_per_dispatch=spd, windowed=elastic,
        overlap_microbatches=max(1, ovl))
    if fault_plan is None and resilience is not None and resilience.faults:
        fault_plan = resilience.fault_plan()   # resolve ONCE: the elastic
        #   rebuild must re-wrap the same schedule, not a fresh counter's

    def _make_batches(n):
        return sharded_batches(tok, train_cfg.batch_size, train_cfg.seq_len,
                               n, shard_skip=5000, seed=train_cfg.seed)

    if elastic:
        from ..resilience.elastic import ElasticController

        def _rewrap(fn, start=0):
            return _apply_resilience(fn, resilience, fault_plan, ckpt,
                                     stats, start=start)

        # No layer_divisor: the TP model axis never re-partitions —
        # survivor_submesh either drops whole data rows or declares a
        # model-axis loss unrecoverable.
        controller = ElasticController(
            mesh, build=_build_elastic, rewrap=_rewrap,
            make_batches=_make_batches, ckpt=ckpt,
            mirror_every=resilience.mirror_every, stats=stats,
            telemetry=telemetry, log_fn=log_fn)
        return _run_elastic_loop(
            controller, _rewrap(step_fn), state, _make_batches(n_data),
            train_cfg, n_data=n_data, start_step=start_step, ckpt=ckpt,
            checkpoint_every=checkpoint_every, loss_sink=loss_sink,
            sink_every=sink_every, log_every=log_every, log_fn=log_fn,
            warmup_steps_excluded=warmup_steps_excluded, stats=stats,
            telemetry=telemetry, steps_per_dispatch=spd,
            window_shard_fn=window_shard, on_checkpoint=on_checkpoint,
            scale_hook=scale_hook)
    step_fn = _apply_resilience(step_fn, resilience, fault_plan, ckpt, stats)

    batches = _make_batches(n_data)
    return _run_loop(step_fn, state, batches, train_cfg,
                     lambda b: tp.shard_batch(mesh, b), n_data=n_data,
                     start_step=start_step, ckpt=ckpt,
                     checkpoint_every=checkpoint_every, loss_sink=loss_sink,
                     sink_every=sink_every, log_every=log_every,
                     log_fn=log_fn, comm_profile=comm_profile,
                     warmup_steps_excluded=warmup_steps_excluded,
                     stats=stats, telemetry=telemetry,
                     steps_per_dispatch=spd,
                     window_shard_fn=lambda w: tp.shard_batch_window(mesh, w),
                     numerics=numerics,
                     numerics_every=train_cfg.numerics_every,
                     compile_watch=compile_watch,
                     on_checkpoint=on_checkpoint)


class _Exchange:
    """The step's gradient exchange on every ``train.dispatch`` of
    ``_run_loop`` (docs/COMPONENTS.md): the integer counters
    ``sync_payload_bytes``, ``sync_wire_bytes`` and ``collectives``, from
    the ``comm`` records the step's own trace leaves, so the step is never
    traced a second time. Each dispatch runs inside ``comm.collecting()``;
    the one that traced (the first) adds the counters through
    ``set_metadata`` at its end, and later ones, which hit the jit cache
    and leave no records, pass them as keyword ints. Where the step was
    traced before the loop (the manifest's ``measure_comm``), ``profile``
    hands that trace's records in. It sits at the end of the module: the
    lines of the step's call stack (``_run_loop``'s call of the step, the
    trainers' call of ``_run_loop``) are part of the compile cache's key
    (introspect.CompileWatch), and lines added above them would change
    every training program's key."""

    def __init__(self, profile=None):
        from ..telemetry import comm

        self.counters = (None if profile is None
                         else comm.dispatch_counters(profile))

    def dispatch(self, phase, root, it: int):
        """``phase("dispatch", root, "compute", it=it)`` with the counters,
        collecting the records of a dispatch that traces the step."""
        import contextlib

        from ..telemetry import comm

        @contextlib.contextmanager
        def span():
            with phase("dispatch", root, "compute", it=it,
                       **(self.counters or {})) as csp:
                with comm.collecting() as records:
                    yield csp
                if records or self.counters is None:
                    self.counters = comm.dispatch_counters(
                        comm.CommProfile(records))
                    if hasattr(csp, "set_metadata"):  # the profiler's span
                        csp.set_metadata(**self.counters)

        return span()
