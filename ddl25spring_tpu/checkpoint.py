"""Checkpoint / resume via orbax — distributed-aware, sharding-preserving.

The reference has essentially NO persistence: its only checkpointing is a
best-weights `state_dict()` snapshot held in memory and restored at the end
of one training run (reference: lab/tutorial_2a/centralized.py:51,67-70);
there is no torch.save, no distributed checkpointing, no resume (SURVEY.md
§5.4). This module exceeds that cheaply with the TPU-native standard:
orbax writes each shard from the device that owns it (multi-host safe) and
restores arrays directly into the target mesh layout.

Works for every TrainState in the framework — DP-replicated, PP
stage-sharded, TP/EP weight-sharded — because restore takes a template state
whose shapes/shardings define the layout to materialize into.
"""

from __future__ import annotations

import hashlib
import json
import os
import tempfile
from typing import Any, Dict, List, Optional

import jax
import numpy as np
import orbax.checkpoint as ocp
from jax.sharding import NamedSharding, PartitionSpec as P

from .metrics import ResilienceStats
from .resilience.retry import retry_call

MANIFEST_VERSION = 1


def _sha256_file(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


class Checkpointer:
    """Thin wrapper over an orbax CheckpointManager.

    Usage::

        ckpt = Checkpointer(dir, max_to_keep=3)
        ckpt.save(int(state.step), state)          # async-capable save
        state = ckpt.restore(template_state)       # into template's sharding
        step = ckpt.latest_step()                  # None if nothing saved

    Robustness contract (resilience layer): ``save`` retries transient IO
    failures with exponential backoff; ``restore`` falls back past a
    corrupt/unreadable step to the newest step that restores cleanly —
    counted in ``stats.ckpt_fallbacks`` — so a checkpoint truncated by a
    mid-write kill costs ``checkpoint_every`` steps of progress, never the
    run. ``max_to_keep >= 2`` is what makes the fallback non-vacuous.

    Integrity manifests: each save records a per-step JSON manifest
    (``<dir>/digests/<step>.json``) of shard-file SHA-256 digests — written
    once the async save lands (``wait``/``restore``/``close`` flush it) —
    plus the saved leaf shapes/dtypes. ``restore`` verifies digests BEFORE
    handing the step to orbax, so a silent on-disk bit-flip (injectable via
    ``resilience/faults.py``) is detected and skipped as a
    ``ckpt_fallbacks`` fallback instead of restoring poisoned weights
    bit-exactly. Steps saved without a manifest (pre-manifest checkpoints)
    restore unverified, as before.

    Cross-topology restore (elastic re-mesh, resilience/elastic.py): when
    the manifest's saved leaf shapes differ from ``template``'s — a ZeRO-1
    state saved at world size N restored onto M survivors — the step is
    restored at its SAVED shapes (replicated) and resharded into the
    template via ``parallel.dp.reshard_state`` (pad-swap with a hard error
    on non-zero truncated tails, never orbax's silent shape adaptation).
    Counted in ``stats.ckpt_reshards``. When the template lives on a
    ``(data, stage)`` mesh this includes a stage RE-PARTITION: a state
    saved at (D, S) restores onto (D′, S′) via
    ``parallel.pp.repartition_stage_state``'s global-coordinate-id remap
    of the stage-sharded moments / EF residuals, same entry point.
    """

    def __init__(self, directory: str, *, max_to_keep: int = 3,
                 retry_attempts: int = 3, retry_base_delay: float = 0.1,
                 stats: Optional[ResilienceStats] = None):
        self._retry_attempts = max(1, retry_attempts)
        self._retry_base = retry_base_delay
        self.stats = stats if stats is not None else ResilienceStats()
        self.restored_step: Optional[int] = None  # set by restore()
        self._dir = os.path.abspath(directory)
        self._digest_dir = os.path.join(self._dir, "digests")
        # step -> saved leaf metadata, held until the async save lands and
        # the digest manifest can be computed from the on-disk files.
        self._pending_manifests: Dict[int, List[Optional[dict]]] = {}
        self._mgr = ocp.CheckpointManager(
            self._dir,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=max_to_keep, create=True),
        )

    # ------------------------------------------------- integrity manifests

    def _manifest_path(self, step: int) -> str:
        return os.path.join(self._digest_dir, f"{step}.json")

    def _read_manifest(self, step: int) -> Optional[dict]:
        try:
            with open(self._manifest_path(step)) as f:
                m = json.load(f)
            return m if isinstance(m, dict) else None
        except (OSError, ValueError):
            return None

    def _step_files(self, step: int) -> Dict[str, str]:
        """relpath -> abspath for every file under the committed step dir."""
        root = os.path.join(self._dir, str(step))
        out = {}
        for base, _, files in os.walk(root):
            for fname in files:
                p = os.path.join(base, fname)
                out[os.path.relpath(p, root)] = p
        return out

    def _flush_manifests(self) -> None:
        """Write digest manifests for landed saves; prune manifests of
        steps the manager has since deleted (max_to_keep). Call only after
        ``wait_until_finished`` — digests of in-flight files would be
        digests of half-written bytes."""
        live = set(self.all_steps())
        for step in list(self._pending_manifests):
            leaves = self._pending_manifests.pop(step)
            if step not in live:
                continue             # evicted before landing; nothing to do
            try:
                files = {rel: _sha256_file(p)
                         for rel, p in self._step_files(step).items()}
                os.makedirs(self._digest_dir, exist_ok=True)
                fd, tmp = tempfile.mkstemp(dir=self._digest_dir,
                                           suffix=".json.tmp")
                with os.fdopen(fd, "w") as f:
                    json.dump({"version": MANIFEST_VERSION, "step": step,
                               "files": files, "leaves": leaves}, f)
                os.replace(tmp, self._manifest_path(step))
            except OSError:
                pass                 # integrity extras must not sink a save
        try:
            for name in os.listdir(self._digest_dir):
                stem = name.partition(".")[0]
                if stem.isdigit() and int(stem) not in live:
                    os.unlink(os.path.join(self._digest_dir, name))
        except OSError:
            pass

    def _verify_digests(self, step: int) -> Optional[str]:
        """None if the step's files match its manifest (or no manifest
        exists — legacy steps restore unverified); else a description of
        the first mismatch.

        Deliberately re-hashes even steps this process digested moments
        ago in ``_flush_manifests``: the threat model is on-disk mutation
        AFTER the bytes landed (bit rot, another process, an injected
        fault between save and restore), and a skip-if-recently-hashed
        fast path would be blind to exactly that window. The cost is one
        extra read+hash per restored step in the save-then-restore-same-
        process case (StepGuard rollback, elastic recovery)."""
        manifest = self._read_manifest(step)
        if manifest is None or not isinstance(manifest.get("files"), dict):
            return None
        on_disk = self._step_files(step)
        for rel, want in manifest["files"].items():
            p = on_disk.get(rel)
            if p is None:
                return f"missing shard file {rel!r}"
            try:
                got = _sha256_file(p)
            except OSError as e:
                return f"unreadable shard file {rel!r}: {e}"
            if got != want:
                return f"digest mismatch in {rel!r}"
        return None

    def _count_retry(self, attempt: int, exc: BaseException) -> None:
        self.stats.retries += 1

    def save(self, step: int, state: Any, *, force: bool = False,
             overwrite: bool = False) -> bool:
        """Persist a pytree (e.g. a TrainState) at ``step``. Returns as soon
        as the arrays are snapshotted; serialization/IO continues in the
        background (orbax async) — call ``wait()`` to block, or rely on the
        lazy waits in restore()/close(). Transient failures (disk pressure,
        a previous async save erroring out at the enqueue barrier) are
        retried with backoff before surfacing.

        ``overwrite=True`` deletes any existing step ``step`` first. Only
        for callers re-treading step indices after a corrupt-latest fallback
        resume: the on-disk entry is then a stale (possibly the corrupt)
        remnant of the pre-fallback lineage, and a blind save would be an
        orbax StepAlreadyExistsError. Default False so double-save bugs
        still fail loudly. An overwrite first waits for saves in flight:
        the entry it replaces may be one of them (a periodic save at a
        chunk edge, then a re-mesh persisting the new layout under the
        same index), and deleting under its commit races the rename."""
        if overwrite:
            self._mgr.wait_until_finished()
        if step in self.all_steps():
            if not overwrite:
                # Fail fast and outside the retry loop: a double-save is a
                # deterministic caller bug, and retrying it would both delay
                # the failure and count phantom IO retries into the stats.
                raise ValueError(
                    f"checkpoint step {step} already exists "
                    f"(pass overwrite=True to replace a stale entry)")
            self._mgr.delete(step)
            self._pending_manifests.pop(step, None)
            try:
                os.unlink(self._manifest_path(step))
            except OSError:
                pass
        ok = retry_call(
            self._mgr.save, step, args=ocp.args.StandardSave(state),
            force=force, attempts=self._retry_attempts,
            base=self._retry_base, seed=step, on_retry=self._count_retry)
        # Leaf metadata for the integrity/reshard manifest, captured NOW
        # (shapes/dtypes only — no device sync); digests wait for the
        # async write to land (_flush_manifests).
        self._pending_manifests[step] = [
            {"shape": list(x.shape), "dtype": str(x.dtype)}
            if isinstance(x, jax.Array) else None
            for x in jax.tree.leaves(state)]
        return ok

    def wait(self) -> None:
        self._mgr.wait_until_finished()
        self._flush_manifests()

    def restore(self, template: Any, *, step: Optional[int] = None) -> Any:
        """Restore into ``template``'s structure, dtypes, and shardings.

        ``template`` is a live pytree with the desired layout (typically a
        freshly built TrainState on the current mesh — its values are only
        read for shape/sharding). Defaults to the latest step; if that step
        is corrupt/unreadable (truncated by a kill, garbled on disk, or
        failing its digest manifest), falls back to the next-newest step
        that restores cleanly — each skipped step counts into
        ``stats.ckpt_fallbacks``. An explicitly requested ``step`` does NOT
        fall back: the caller named it, so failing loudly is correct.

        A step whose manifest records leaf shapes DIFFERENT from the
        template's (saved at another data-parallel world size) is restored
        at its saved shapes and resharded into the template — see the class
        docstring's cross-topology contract.
        """
        self._mgr.wait_until_finished()   # flush any in-flight async save
        self._flush_manifests()

        def abstract(x):
            if isinstance(x, jax.Array):
                return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
            return x

        target = jax.tree.map(abstract, template)

        def place(restored):
            # Belt-and-braces: orbax can return scalar/replicated leaves on
            # a single device; re-place every leaf into the template's
            # sharding so the result is directly usable by the mesh-compiled
            # train step.
            return jax.tree.map(
                lambda r, t: (jax.device_put(r, t.sharding)
                              if isinstance(t, jax.Array) else r),
                restored, template)

        def restore_one(s: int):
            bad = self._verify_digests(s)
            if bad is not None:
                raise ValueError(
                    f"checkpoint step {s} failed integrity check: {bad}")
            saved_target = self._saved_shape_target(s, template)
            if saved_target is None:      # shapes match: the common case
                return place(self._mgr.restore(
                    s, args=ocp.args.StandardRestore(target)))
            # Cross-topology: restore at SAVED shapes (replicated), then
            # pad-swap + rescatter into the template's mesh — never let
            # orbax silently truncate into a smaller target.
            from .parallel.dp import reshard_state
            restored = self._mgr.restore(
                s, args=ocp.args.StandardRestore(saved_target))
            out = reshard_state(restored, template)
            self.stats.ckpt_reshards += 1
            return out

        if step is not None:
            restored = restore_one(step)
            self.restored_step = step  # only after the restore succeeded
            return restored

        candidates = sorted(self.all_steps(), reverse=True)
        if not candidates:
            raise FileNotFoundError("no checkpoint found")
        last_exc: Optional[BaseException] = None
        for s in candidates:
            try:
                restored = restore_one(s)
            except Exception as e:  # corrupt/garbled/digest-failed step
                last_exc = e
                self.stats.ckpt_fallbacks += 1
                continue
            self.restored_step = s  # which step actually won (≤ latest_step)
            return restored
        raise FileNotFoundError(
            f"all {len(candidates)} checkpoint steps failed to restore "
            f"(newest error: {last_exc!r})") from last_exc

    def _saved_shape_target(self, step: int, template):
        """An abstract restore target at the manifest's SAVED leaf shapes
        (template structure, replicated sharding on the template's mesh) —
        or None when shapes already match the template / no manifest
        records them (legacy steps restore as before)."""
        manifest = self._read_manifest(step)
        leaves_meta = (manifest or {}).get("leaves")
        t_leaves, treedef = jax.tree.flatten(template)
        if (not isinstance(leaves_meta, list)
                or len(leaves_meta) != len(t_leaves)):
            return None
        changed = False
        out = []
        for t, meta in zip(t_leaves, leaves_meta):
            if not isinstance(t, jax.Array) or meta is None:
                out.append(jax.ShapeDtypeStruct(t.shape, t.dtype,
                                                sharding=t.sharding)
                           if isinstance(t, jax.Array) else t)
                continue
            shape = tuple(meta["shape"])
            if shape == t.shape:
                out.append(jax.ShapeDtypeStruct(t.shape, t.dtype,
                                                sharding=t.sharding))
                continue
            changed = True
            mesh = getattr(t.sharding, "mesh", None)
            repl = NamedSharding(mesh, P()) if mesh is not None else None
            out.append(jax.ShapeDtypeStruct(shape, np.dtype(meta["dtype"]),
                                            sharding=repl))
        return jax.tree.unflatten(treedef, out) if changed else None

    def latest_step(self) -> Optional[int]:
        return self._mgr.latest_step()

    def all_steps(self):
        return list(self._mgr.all_steps())

    def close(self) -> None:
        try:
            self._mgr.wait_until_finished()
            self._flush_manifests()
        except Exception:
            pass              # closing must succeed even on a broken disk
        self._mgr.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def save_best(path: str, params: Any) -> None:
    """The reference's best-weights idiom (centralized.py:51) as a one-shot
    file save: host-gather params and write an .npz.

    Atomic: the archive is written to a temp file in the target directory
    and ``os.replace``d into place, so a mid-write kill leaves either the
    previous best intact or the new one — never a truncated .npz (np.savez
    writes incrementally, so a plain in-place save can be killed half-way)."""
    import tempfile

    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    arrays = {jax.tree_util.keystr(p): np.asarray(v) for p, v in flat}
    fd, tmp = tempfile.mkstemp(
        dir=os.path.dirname(os.path.abspath(path)), suffix=".npz.tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            np.savez(f, **arrays)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def load_best(path: str, template: Any) -> Any:
    """Inverse of save_best: load the .npz back into template's structure."""
    data = np.load(path)
    flat, treedef = jax.tree_util.tree_flatten_with_path(template)
    leaves = [jax.device_put(data[jax.tree_util.keystr(p)],
                             v.sharding if isinstance(v, jax.Array) else None)
              for p, v in flat]
    return jax.tree_util.tree_unflatten(treedef, leaves)
