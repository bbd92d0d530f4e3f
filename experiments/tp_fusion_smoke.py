"""TP-fusion smoke: the TP composition column's claims, checked (ISSUE 18).

The CI-sized proof (tier1.yml) that TP now carries the fused-dispatch +
overlapped/compressed sync column, on a 4-virtual-device
``(data=2, model=2)`` CPU mesh — the pp_fusion_smoke contract applied to
the TP column:

1. the MODEL-AXIS activation wire of the relaxed PSA modes
   (TrainConfig.psa = "defer:L" / "int8_ef") is ≤ the ANALYTIC budget
   (tp.psa_sync_wire_bytes, whose docstring states the formulas) AND
   below the full-sync baseline measured
   from the SAME run family (psa="full" routes the identical sync
   positions through the telemetry wrappers, so the comparison is
   trace-measured, not hand-computed);
2. the DP×TP ring + delta-gather accounting of the composed
   ``int8_ef + zero1 + scan4`` driver (tp.make_tp_overlap_multi_step) is
   EXACT: the profile's trips × payloads equal the analytic
   K·M·(n−1)·chunk_bytes (+ per-hop scale sidecars, + K·(n−1)·chunk
   gather) formulas to the byte;
3. zero retraces across the psa × K grid (tp.make_tp_multi_step) AND the
   wire × K grid at zero1 through the overlap driver
   (introspect.CompileWatch): each config compiles exactly ONE program
   over repeated same-shape dispatches;
4. the TRAINER's compile events carry the TP window size
   (``steps_per_dispatch`` stamped per compiling call, tail chunks with
   their ACTUAL smaller window) — checked end-to-end through
   train_llm_tp + telemetry.

Wire-byte rows land in the JSON artifact in the bench_compare row shape
({"metric": "wire_bytes_model_per_train_step", ...}) — the ``wire_bytes``
prefix pins the lower-is-better direction, so the PSA wire-reduction
claim is trajectory-gated exactly like DP's and PP's. Diagnostics live IN
the JSON (the tier1 don't-clobber contract); exit 0 only when every
check holds.

    python -m experiments.tp_fusion_smoke --out tp-fusion.json
"""

from __future__ import annotations

import argparse
import json
import sys


def run(out_path: str) -> int:
    from ._cpu_pin import pin_cpu_virtual
    pin_cpu_virtual()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ddl25spring_tpu.config import LlamaConfig
    from ddl25spring_tpu.models import llama
    from ddl25spring_tpu.parallel import make_mesh, tp
    from ddl25spring_tpu.telemetry import introspect, measure_comm

    n, T, K = 2, 2, 4                          # data, model(tp), scan
    mesh = make_mesh({"data": n, "model": T}, devices=jax.devices()[:n * T])
    cfg = LlamaConfig(vocab_size=259, dmodel=32, num_heads=2, n_layers=4,
                      ctx_size=16)
    opt = lambda: optax.adam(1e-3)  # noqa: E731

    def fresh_params():
        return llama.init_llama(jax.random.key(0), cfg)

    bsz = 4                                    # per data shard
    batch_sds = jax.ShapeDtypeStruct((n * bsz, cfg.ctx_size), jnp.int32)
    window_sds = jax.ShapeDtypeStruct((K, n * bsz, cfg.ctx_size), jnp.int32)

    checks, rows, profiles = {}, [], {}

    # ---- PSA: measured model-axis activation wire vs analytic budget ----
    # psa="full" is the measured baseline: the same sync positions as the
    # legacy bitwise path, routed through comm.psum so the bytes are
    # visible. The relaxed modes must land ≤ their analytic budget AND
    # strictly below the measured full-sync wire — both from trace-time
    # profiles of the same model/mesh.
    def psa_wire(psa):
        state, step = tp.make_tp_step(cfg, opt(), mesh, fresh_params(),
                                      psa=psa,
                                      batch_shape=(bsz, cfg.ctx_size))
        prof = measure_comm(step, state, batch_sds)
        by = prof.by_label()
        labels = ("psa_full_sync", "psa_defer_sync", "psa_act_int8",
                  "psa_act_scale")
        wire = sum(by[l]["wire_bytes_per_device"] for l in labels
                   if l in by)
        return wire, prof

    psa_checks = {}
    full_wire, full_prof = psa_wire("full")
    profiles["tp_psa_full"] = full_prof.as_dict()
    full_budget = tp.psa_sync_wire_bytes(cfg, "full", T, bsz, cfg.ctx_size)
    psa_checks["full"] = {"measured": full_wire, "budget": full_budget,
                          "ok": full_wire == full_budget}
    rows.append({"metric": "wire_bytes_model_per_train_step",
                 "value": full_wire, "unit": "bytes/device/step",
                 "platform": "cpu", "variant": "tp2-psa-full"})
    for psa in ("defer:2", "int8_ef"):
        wire, prof = psa_wire(psa)
        budget = tp.psa_sync_wire_bytes(cfg, psa, T, bsz, cfg.ctx_size)
        psa_checks[psa] = {
            "measured": wire, "budget": budget,
            "full_sync_measured": full_wire,
            "reduction_vs_full": wire / full_wire,
            "ok": bool(wire <= budget and wire < full_wire)}
        profiles[f"tp_psa_{psa.replace(':', '')}"] = prof.as_dict()
        rows.append({"metric": "wire_bytes_model_per_train_step",
                     "value": wire, "unit": "bytes/device/step",
                     "platform": "cpu",
                     "variant": f"tp2-psa-{psa.replace(':', '')}"})
    checks["psa_wire_budget"] = {
        "modes": psa_checks,
        "ok": all(v["ok"] for v in psa_checks.values())}

    # ---- exact DP×TP ring + gather accounting vs analytic formulas ----
    cand_state, cand_step = tp.make_tp_overlap_multi_step(
        cfg, opt(), mesh, fresh_params(), aggregation="zero1",
        wire="int8_ef", overlap_microbatches=1)
    cand_prof = measure_comm(cand_step, cand_state, window_sds)
    profiles["tp_int8ef_zero1_scan4"] = cand_prof.as_dict(
        steps_per_dispatch=K)
    from ddl25spring_tpu.parallel.tp import _tp_flat_geometry
    _, _, local, _ = _tp_flat_geometry(mesh, fresh_params())
    by = cand_prof.by_label()
    got = {"ring_payload": by["tp_ring_grad_int8"]["payload_bytes"],
           "ring_scales": by["tp_ring_grad_scale"]["payload_bytes"],
           "ring_wire": by["tp_ring_grad_int8"]["wire_bytes_per_device"],
           "gather_wire":
               by["tp_delta_gather_int8"]["wire_bytes_per_device"]}
    want = {"ring_payload": K * 1 * (n - 1) * local,  # K·M·(n−1)·chunk int8
            "ring_scales": K * 1 * (n - 1) * 4,       # one fp32 per hop
            "ring_wire": K * 1 * (n - 1) * local,     # ppermute: wire==payload
            "gather_wire": K * (n - 1) * local}       # int8 delta all-gather
    checks["tp_ring_analytic"] = {"got": got, "want": want,
                                  "ok": got == want}

    # ---- zero retraces: psa × K grid through the fused scan driver ----
    rng = np.random.default_rng(0)
    psa_retraces = {}
    for psa in ("", "full", "defer:2", "int8_ef"):
        for k in (1, 2):
            state, step = tp.make_tp_multi_step(
                cfg, opt(), mesh, fresh_params(), psa=psa,
                batch_shape=(bsz, cfg.ctx_size))
            step = introspect.watch(
                step, name=f"smoke/tp-psa{psa.replace(':', '')}-k{k}",
                max_caches=1)
            window = rng.integers(
                0, cfg.vocab_size,
                size=(k, n * bsz, cfg.ctx_size)).astype(np.int32)
            loss = None
            for _ in range(3):
                state, losses = step(state,
                                     tp.shard_batch_window(mesh, window))
                loss = float(np.asarray(losses)[-1])
            psa_retraces[f"psa{psa.replace(':', '') or 'off'}-k{k}"] = {
                "compiles": len(step.compiles),
                "retraces": sum(1 for c in step.compiles if c.retrace),
                "final_loss": loss,
                "ok": bool(len(step.compiles) == 1
                           and not any(c.retrace for c in step.compiles)
                           and np.isfinite(loss))}
    checks["psa_retraces"] = {
        "grid": psa_retraces,
        "ok": all(v["ok"] for v in psa_retraces.values())}

    # ---- zero retraces: wire × K grid through the overlap driver ----
    wire_retraces = {}
    for wire in ("fp32", "bf16", "int8_ef"):
        for k in (1, 2):
            state, step = tp.make_tp_overlap_multi_step(
                cfg, opt(), mesh, fresh_params(), aggregation="zero1",
                wire=wire, overlap_microbatches=1)
            step = introspect.watch(step, name=f"smoke/tp-{wire}-k{k}",
                                    max_caches=1)
            window = rng.integers(
                0, cfg.vocab_size,
                size=(k, n * bsz, cfg.ctx_size)).astype(np.int32)
            loss = None
            for _ in range(3):
                state, losses = step(state,
                                     tp.shard_batch_window(mesh, window))
                loss = float(np.asarray(losses)[-1])
            wire_retraces[f"{wire}-k{k}"] = {
                "compiles": len(step.compiles),
                "retraces": sum(1 for c in step.compiles if c.retrace),
                "final_loss": loss,
                "ok": bool(len(step.compiles) == 1
                           and not any(c.retrace for c in step.compiles)
                           and np.isfinite(loss))}
    checks["overlap_retraces"] = {
        "grid": wire_retraces,
        "ok": all(v["ok"] for v in wire_retraces.values())}

    # ---- trainer compile events carry the TP window size ----
    # End-to-end through train_llm_tp: iters=3 at K=2 runs one full chunk
    # and one tail chunk — two compiles, stamped 2 and 1, so slo_monitor's
    # per-step MFU normalization cannot misread the tail as a full-K
    # program (the DP/PP chunked trainers' contract).
    import os
    import tempfile

    from ddl25spring_tpu.config import TrainConfig
    from ddl25spring_tpu.telemetry import Telemetry
    from ddl25spring_tpu.tokenizers import ByteTokenizer
    from ddl25spring_tpu.train.llm import train_llm_tp

    tdir = tempfile.mkdtemp(prefix="tp-fusion-smoke-")
    tel = Telemetry(tdir)
    try:
        train_llm_tp(cfg,
                     TrainConfig(batch_size=bsz, seq_len=cfg.ctx_size,
                                 iters=3, lr=3e-3, data=n, model=T,
                                 psa="int8_ef", steps_per_dispatch=2),
                     mesh=mesh, tokenizer=ByteTokenizer(), log_every=0,
                     telemetry=tel)
    finally:
        tel.close()
    compile_events = []
    with open(os.path.join(tel.out_dir, "events.jsonl")) as f:
        for line in f:
            e = json.loads(line)
            if e.get("type") == "compile" and \
                    str(e.get("name", "")).startswith("train/tp"):
                compile_events.append(e)
    stamped = sorted((e.get("steps_per_dispatch") or 0)
                     for e in compile_events)
    checks["trainer_compile_meta"] = {
        "events": [{"name": e.get("name"),
                    "steps_per_dispatch": e.get("steps_per_dispatch")}
                   for e in compile_events],
        "want_window_sizes": [1, 2],
        "ok": stamped == [1, 2]}

    ok = all(c["ok"] for c in checks.values())
    doc = {"ok": ok, "n_data": n, "tp": T, "steps_per_dispatch": K,
           "model": {"dmodel": cfg.dmodel, "n_layers": cfg.n_layers,
                     "vocab": cfg.vocab_size, "ctx": cfg.ctx_size},
           "checks": checks, "rows": rows, "profiles": profiles}
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=2)
    int8_red = checks["psa_wire_budget"]["modes"]["int8_ef"][
        "reduction_vs_full"]
    print(f"tp-fusion smoke: psa int8 model-axis wire "
          f"{int8_red:.3f}x of full sync (budget-gated), "
          f"ring accounting "
          f"{'exact' if checks['tp_ring_analytic']['ok'] else 'WRONG'}, "
          f"retraces {'clean' if checks['psa_retraces']['ok'] and checks['overlap_retraces']['ok'] else 'DIRTY'}, "
          f"compile meta "
          f"{'stamped' if checks['trainer_compile_meta']['ok'] else 'MISSING'} "
          f"-> {out_path}", file=sys.stderr)
    return 0 if ok else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", default="tp-fusion.json")
    a = ap.parse_args(argv)
    return run(a.out)


if __name__ == "__main__":
    sys.exit(main())
