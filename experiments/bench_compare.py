"""Trajectory comparator over JSON rows the smokes print.

Not the yardstick: the driver judges a PR by ``BENCHMARK.json`` and
``python3 benchmarks/run.py --workload <cell>`` (PERF.md). This tool
compares the ``rows`` the CI smokes write (``experiments/*_smoke.py``,
``serving_bench.py``: counts such as wire bytes a step, tokens per
dispatch, overlap fraction) between named files: a bare row object, JSON
lines between human lines, or a wrapper ``{"parsed": {row}, "tail": <text
with JSON lines>}``. Pure stdlib, no jax. It prints the trajectory per
(metric, platform, variant) group, and exits nonzero when the newest
comparable row regresses more than ``--max-regression`` percent against
the best row of the SAME platform tag: a CPU count must never be judged
against a TPU row.
Rows are direction-aware: throughput-like metrics regress downward, while
``wire_bytes_*`` / ``payload_bytes_*`` rows (the comm-wire smoke's) are
lower-is-better and gate when the candidate RISES above the best (lowest)
committed row — see ``lower_is_better``.

``--warn-only`` prints the verdict but always exits 0.

Example:
    python -m experiments.bench_compare --candidate bench-headline.json \\
        --max-regression 20 --warn-only
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import Any, Dict, List, Optional, Tuple


# Attainment-style FIELDS on headline rows, promoted to their own
# comparable rows. Higher is better for both (like every row here), but
# they are only meaningful same-platform — an MFU measured against the
# calibrated CPU baseline must NEVER gate against the TPU round-4 0.310
# — so a derived row REQUIRES an explicit platform tag: a row without
# one gets no derived entry rather than landing in a "None" bucket both
# platforms would share.
DERIVED_FIELDS = ("mfu", "attainment")

# Direction map. Most headline rows are throughput-like (higher is
# better) — that default covers ``tokens_per_dispatch`` (the serving
# bench's speculative-decode row: MORE tokens per target dispatch is the
# win, so a draft regression gates like a tok/s drop) — but the
# comm-wire smoke's byte rows regress UPWARD — more bytes is worse — and
# judging them higher-is-better would wave a wire-bytes regression
# through as an "improvement". A metric whose name starts with one of
# these prefixes is compared against the best (LOWEST) committed row and
# gates when the candidate rises above it by more than the budget.
# ``remesh_seconds`` / ``steps_replayed`` are the elasticity smokes'
# recovery-cost rows (elastic_smoke / autoscale_smoke): slower re-mesh or
# more re-trained steps is the regression. ``peak_`` covers the memory
# smoke's footprint rows (``peak_device_bytes_*`` / ``peak_rss_bytes_*``,
# schema v9): a run whose peak bytes grew is the memory regression the
# observability tentpole exists to catch. ``wire_bytes`` also pins the
# TP-fusion smoke's ``wire_bytes_model_per_train_step`` rows (ISSUE 18):
# the model-axis activation wire under the PSA modes must only ever
# trend DOWN vs the committed history, same as the data-axis ring rows.
# ``overlap_fraction`` (the comm-wire smoke's bucketed-backward row,
# ISSUE 19: the share of ring hops whose dispatch is
# dataflow-independent of the not-yet-materialized tail of the gradient)
# is deliberately NOT in this tuple — MORE overlap is the win, so it
# keeps the higher-is-better default and gates when the candidate's
# overlap window SHRINKS below the best committed row (pinned in
# tests/test_experiments.py).
LOWER_IS_BETTER_PREFIXES = ("wire_bytes", "payload_bytes",
                            "remesh_seconds", "steps_replayed", "peak_")


def lower_is_better(metric: str) -> bool:
    """True for metrics where a SMALLER value is the better one."""
    return str(metric).startswith(LOWER_IS_BETTER_PREFIXES)


def parse_rows(path: str) -> List[Dict[str, Any]]:
    """Headline rows from one file, tolerating all three shapes: the
    wrapper (``parsed``, plus any JSON lines in ``tail``), a smoke's
    stdout (human lines interleaved with JSON rows), or a bare row
    object. A row is any JSON object with ``metric`` and a numeric
    ``value``. Rows carrying a numeric ``mfu``/``attainment`` field AND a
    platform tag additionally yield a derived row per field (see
    ``DERIVED_FIELDS``)."""
    with open(path) as f:
        text = f.read()
    rows: List[Dict[str, Any]] = []

    def _add(obj):
        if (isinstance(obj, dict) and "metric" in obj
                and isinstance(obj.get("value"), (int, float))):
            rows.append(obj)
            for fld in DERIVED_FIELDS:
                v = obj.get(fld)
                if (isinstance(v, (int, float)) and v > 0
                        and obj.get("platform") is not None):
                    rows.append({"metric": fld, "value": float(v),
                                 "platform": obj["platform"],
                                 "variant": obj.get("variant")})

    try:
        doc = json.loads(text)
    except ValueError:
        doc = None
    if isinstance(doc, dict):
        _add(doc)
        _add(doc.get("parsed"))
        # Smoke artifacts (e.g. comm-wire.json) carry a "rows" list of
        # row objects — the comm-wire smoke's wire-byte rows enter the
        # trajectory through here.
        rows_field = doc.get("rows")
        if isinstance(rows_field, list):
            for obj in rows_field:
                _add(obj)
        text = doc.get("tail") or ""
    for line in text.splitlines():
        line = line.strip()
        if line.startswith("{"):
            try:
                _add(json.loads(line))
            except ValueError:
                pass
    # De-dup (the wrapper's parsed row usually re-appears in its tail).
    seen, out = set(), []
    for r in rows:
        key = json.dumps(r, sort_keys=True)
        if key not in seen:
            seen.add(key)
            out.append(r)
    return out


def _fmt_val(v: float) -> str:
    """Throughput rows are 6-digit integers; derived mfu/attainment rows
    live in [0, 1] — one format hides the latter as 0.0."""
    return f"{v:>14,.1f}" if abs(v) >= 10 else f"{v:>14.4f}"


def row_key(row: Dict[str, Any]) -> Tuple[str, str, str]:
    """Comparability key: rows measured on different platforms (or bench
    variants) are different experiments, not a trajectory."""
    return (str(row.get("metric")), str(row.get("platform")),
            str(row.get("variant")))


def compare(files: List[str], candidate: Optional[str],
            max_regression_pct: float) -> Tuple[List[str], List[str]]:
    """Returns (report lines, regression messages). Regressions are
    judged candidate-vs-best-committed per key; with no candidate, the
    newest committed file is judged against the best of the older ones."""
    history: Dict[Tuple[str, str, str], List[Tuple[str, float]]] = {}
    ordered = sorted(files)
    for path in ordered:
        for row in parse_rows(path):
            history.setdefault(row_key(row), []).append(
                (os.path.basename(path), float(row["value"])))
    cand_rows: Dict[Tuple[str, str, str], Tuple[str, float]] = {}
    if candidate:
        for row in parse_rows(candidate):
            cand_rows[row_key(row)] = (os.path.basename(candidate),
                                       float(row["value"]))

    lines, regressions = [], []
    keys = sorted(set(history) | set(cand_rows))
    for key in keys:
        metric, platform, variant = key
        lines.append(f"{metric} [{platform} / {variant}]")
        traj = history.get(key, [])
        prev = None
        for name, value in traj:
            delta = ("" if prev in (None, 0)
                     else f"  ({100 * (value - prev) / prev:+.1f}%)")
            lines.append(f"  {name:24s} {_fmt_val(value)}{delta}")
            prev = value
        judged = cand_rows.get(key)
        baseline_pool = traj
        if judged is None and len(traj) >= 2:
            judged, baseline_pool = traj[-1], traj[:-1]
        if judged is not None and baseline_pool:
            lower = lower_is_better(metric)
            best_name, best = (min if lower else max)(
                baseline_pool, key=lambda nv: nv[1])
            name, value = judged
            delta_pct = 100 * (value - best) / best if best else 0.0
            # "How much worse", direction-aware: for lower-is-better rows
            # a POSITIVE delta (more bytes) is the regression.
            worse_pct = delta_pct if lower else -delta_pct
            verdict = "ok"
            if worse_pct > max_regression_pct:
                verdict = "REGRESSION"
                regressions.append(
                    f"{metric} [{platform} / {variant}]: {name} = "
                    f"{value:,.1f} is {worse_pct:.1f}% "
                    f"{'above' if lower else 'below'} best "
                    f"committed {best:,.1f} ({best_name}) — budget "
                    f"{max_regression_pct:.0f}%")
            lines.append(f"  {name:24s} {_fmt_val(value)}  "
                         f"({delta_pct:+.1f}% vs best {best_name}) "
                         f"[{verdict}]")
        elif judged is not None:
            name, value = judged
            lines.append(f"  {name:24s} {_fmt_val(value)}  "
                         "(no comparable committed row — new "
                         "platform/variant, nothing to judge against)")
    return lines, regressions


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("files", nargs="*",
                    help="committed bench JSONs (default: BENCH_r*.json "
                         "in the repo root / cwd)")
    ap.add_argument("--candidate", default=None,
                    help="the row under judgment (e.g. the CI smoke's "
                         "bench-headline.json)")
    ap.add_argument("--max-regression", type=float, default=20.0,
                    help="tolerated drop (percent) vs the best committed "
                         "same-platform row")
    ap.add_argument("--warn-only", action="store_true",
                    help="print the verdict but always exit 0 (CI smoke "
                         "mode: QUICK-bench noise must not gate merges)")
    a = ap.parse_args(argv)

    files = a.files or sorted(glob.glob("BENCH_r*.json"))
    if not files and not a.candidate:
        print("no BENCH_r*.json found and no --candidate given",
              file=sys.stderr)
        return 2
    lines, regressions = compare(files, a.candidate, a.max_regression)
    print("\n".join(lines) if lines else "no comparable rows found")
    if regressions:
        for r in regressions:
            print(f"REGRESSION: {r}", file=sys.stderr)
        return 0 if a.warn_only else 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
