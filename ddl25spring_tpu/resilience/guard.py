"""StepGuard: self-healing wrapper around any jitted train step.

``step_fn(state, batch) -> (state, loss)`` in, same signature out, plus:

- **all-finite check** on the loss and the updated parameters (the image of
  the gradients through the optimizer — a NaN/Inf gradient poisons every
  coordinate any standard update rule touches);
- **skip-and-count**: a bad step is discarded — the returned state is
  numerically identical to the pre-step state — and ``stats.skipped_steps``
  increments, so the fault is visible without being fatal;
- **EMA update-norm anomaly detector**: a step whose parameter-delta norm
  exceeds ``anomaly_factor`` × the running EMA (after ``ema_warmup`` good
  steps) is treated as a spike (exploding gradient, corrupted allreduce)
  and skipped even though it is finite;
- **rollback**: after ``max_consecutive_bad`` consecutive bad steps, restore
  the newest valid checkpoint (via ``Checkpointer.restore``'s
  corrupt-step fallback) instead of skipping forever. Rollback restores
  *weights only*; the caller's loop (and its data stream) continues forward,
  so the faulted window's batches are consumed-not-learned — skip-and-count
  semantics extended to a window, keeping checkpoint step indices equal to
  stream positions (what deterministic resume requires; see
  train/llm.py:_run_loop).

Fault-free transparency: on a good step the guard returns ``step_fn``'s
outputs untouched, so a guarded run is bit-identical to an unguarded one
(asserted in tests/test_resilience.py). The cost is one defensive device
copy of the state per step — required because every step factory in
parallel/ donates its input buffers (``donate_argnums=(0,)``), so the
pre-step state would otherwise be unreadable for skip/rollback — plus one
host sync for the finiteness verdict. What the two cost a step is not
measured at published widths: no cell runs a guarded step (ROADMAP D4).

For a sync-free in-step alternative (skip only, no EMA/rollback), see
``parallel/dp.py``'s ``guard_nonfinite`` — the post-allreduce finiteness
guard fused into the step itself (the zero1 variant adds a 4-byte psum so
every replica agrees on the verdict before applying its slice update).

Chunked stepping (train/llm.py ``steps_per_dispatch`` > 1): the guard
wraps the fused K-step driver unchanged — ``loss`` is then the scan's [K]
per-step vector and the verdict/skip/rollback granularity is one DISPATCH.
A bad dispatch skips (consumes-not-learns) all K of its steps, which is
why ``stats.skipped_steps`` counts ``loss.size`` train steps per skip
while ``anomalies``/``rollbacks`` stay per-event; the EMA detector learns
chunk-level update norms, consistent within a run because the chunk size
is fixed. Stream-position step indexing is untouched, so resume stays
deterministic.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from ..metrics import ResilienceStats


def _tree_copy(tree):
    """Defensive device copy — the donation shield."""
    return jax.tree.map(
        lambda x: jnp.array(x, copy=True) if isinstance(x, jax.Array) else x,
        tree)


@jax.jit
def _verdict(old_params, new_params, loss):
    """(all_finite, update_l2_norm) in one fused program."""
    finite = jnp.isfinite(loss).all()
    sq = jnp.zeros((), jnp.float32)
    for o, n in zip(jax.tree.leaves(old_params), jax.tree.leaves(new_params)):
        d = (n - o).astype(jnp.float32)
        finite &= jnp.all(jnp.isfinite(n))
        sq += jnp.sum(d * d)
    return finite, jnp.sqrt(sq)


class StepGuard:
    """Wraps a train step with skip / anomaly / rollback self-healing.

    Parameters
    ----------
    step_fn: the jitted step, ``(state, batch) -> (state, loss)``. ``state``
        must expose ``.params`` (every TrainState in parallel/ does).
    ckpt: optional ``checkpoint.Checkpointer`` — enables rollback to the
        newest valid on-disk step after ``max_consecutive_bad`` consecutive
        bad steps. Without it the guard skips indefinitely.
    stats: a ``metrics.ResilienceStats`` to count into (one is created if
        omitted; read it back via ``guard.stats``).
    max_consecutive_bad: K — consecutive bad steps before rollback.
    ema_decay / anomaly_factor / ema_warmup: update-norm anomaly detector.
        The EMA only learns from good steps and only fires after
        ``ema_warmup`` of them; ``anomaly_factor <= 0`` disables it.
    """

    def __init__(self, step_fn: Callable, *,
                 ckpt=None,
                 stats: Optional[ResilienceStats] = None,
                 max_consecutive_bad: int = 3,
                 ema_decay: float = 0.98,
                 anomaly_factor: float = 10.0,
                 ema_warmup: int = 20):
        self._step_fn = step_fn
        self._ckpt = ckpt
        self.stats = stats if stats is not None else ResilienceStats()
        self.max_consecutive_bad = max_consecutive_bad
        self.ema_decay = ema_decay
        self.anomaly_factor = anomaly_factor
        self.ema_warmup = ema_warmup
        self._ema: Optional[float] = None
        self._good_steps = 0
        self._consecutive_bad = 0
        self._last_trip: Optional[dict] = None

    def pop_trip(self) -> Optional[dict]:
        """Attribution of the most recent bad step, then clears it: which
        leaf paths of the REJECTED state carried NaN/Inf, whether the loss
        was non-finite, the update norm vs the EMA. The training loop
        attaches this to the ``fault`` event it emits, which is what lets
        a flight-recorder bundle NAME the faulted leaf instead of
        reporting "nonfinite somewhere"."""
        trip, self._last_trip = self._last_trip, None
        return trip

    def __call__(self, state, batch) -> Tuple[Any, jnp.ndarray]:
        old = _tree_copy(state)          # survives the step's donation
        new_state, out = self._step_fn(state, batch)
        # Instrumented steps (telemetry/introspect.py) return
        # (loss, NumericsSummary); the guard verdicts on the loss and
        # passes the pair through untouched either way.
        loss = out[0] if isinstance(out, tuple) else out
        finite, upd_norm = _verdict(old.params, new_state.params, loss)
        ok = bool(finite)
        anomalous = False
        if (ok and self.anomaly_factor > 0 and self._ema is not None
                and self._good_steps >= self.ema_warmup):
            anomalous = float(upd_norm) > self.anomaly_factor * self._ema
        if ok and not anomalous:
            u = float(upd_norm)
            self._ema = (u if self._ema is None
                         else self.ema_decay * self._ema
                         + (1.0 - self.ema_decay) * u)
            self._good_steps += 1
            self._consecutive_bad = 0
            return new_state, out
        # Bad step: count, skip (numerically a no-op), maybe roll back.
        # A chunked dispatch (vector loss) skips loss.size train steps.
        if anomalous:
            self.stats.anomalies += 1
        else:
            self.stats.skipped_steps += int(getattr(loss, "size", 1) or 1)
        # Attribution on the fault path only (it syncs the rejected
        # params): name WHICH leaves went non-finite before the poisoned
        # state is dropped — after the skip the only copy is gone.
        try:
            from ..telemetry.introspect import nonfinite_leaves
            import numpy as np
            self._last_trip = {
                "anomalous": anomalous,
                "loss_nonfinite": not bool(
                    np.isfinite(np.asarray(loss)).all()),
                "update_norm": float(upd_norm),
                "nonfinite_params": nonfinite_leaves(new_state.params),
            }
        except Exception:
            self._last_trip = None
        self._consecutive_bad += 1
        if (self._ckpt is not None
                and self._consecutive_bad >= self.max_consecutive_bad):
            try:
                restored = self._ckpt.restore(old)
            except FileNotFoundError:
                return old, out           # nothing on disk yet; keep skipping
            self.stats.rollbacks += 1
            self._consecutive_bad = 0
            return restored, out
        return old, out
