"""What every cell shares: finding a cell's files by the names in
`BENCHMARK.json`, the device check, the compile cache, quantiles, the
readers of per-layer metrics, and the result line.

A cell is `BENCHMARK.json`'s entry of `workloads`. Its configuration is
`configs/<config>.json`, its traffic `traffic/<traffic>.json`, the limits of
its `correct` `limits/<cell>.json`, each per-layer metric
`metrics/<metric>.json`, which names a reader `readers/<reader>.py` and its
parameters. A later PR adds files and entries; nothing here lists them.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


class BenchError(Exception):
    """The run cannot be made; exit non-zero and print no result."""


def _load_json(*parts: str) -> dict:
    path = os.path.join(*parts)
    if not os.path.exists(path):
        raise BenchError(f"missing {os.path.relpath(path, ROOT)}")
    with open(path, encoding="utf-8") as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def load_cell(name: str) -> Cell:
    """The cell `name` of `BENCHMARK.json` with its files."""
    bench = _load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json "
                         f"(has {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_file = configs[w["config"]]["file"]
    bdir = os.path.join(ROOT, bench["paths"][0])

    def mine(metric: dict, reported: Optional[set]) -> bool:
        if "workloads" in metric:
            return name in metric["workloads"]
        return reported is None or metric["moves"] in reported

    e2e = [m for m in bench["end_to_end"] if mine(m, None)]
    reported = {m["name"] for m in e2e}
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        traffic_name=w["traffic"],
        config=_load_json(ROOT, cfg_file),
        traffic=_load_json(bdir, "traffic", w["traffic"] + ".json"),
        limits=_load_json(bdir, "limits", name + ".json"),
        end_to_end=e2e,
        per_layer=[m for m in bench["per_layer"] if mine(m, reported)])


# ------------------------------------------------------------------ device

def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at `JAX_COMPILATION_CACHE_DIR`
    where that is set, else at `<checkout>/.jax_cache`: a fixed path, so the
    second run of a cell in a checkout finds every program."""
    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    path = placed or os.path.join(ROOT, ".jax_cache")
    if not placed:
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def device_info(chips: int) -> dict:
    """Platform, kind and count as JAX reports them, with the kind's peaks.
    Anything but `chips` TPUs of a kind in `peaks.json` ends the run."""
    import jax

    devs = jax.devices()
    kind, platform = devs[0].device_kind, devs[0].platform
    table = _load_json(BENCH_DIR, "peaks.json")["by_device_kind"]
    if platform != "tpu":
        raise BenchError(f"needs a TPU; JAX found platform {platform!r}")
    if len(devs) < chips:
        raise BenchError(f"needs {chips} chips; JAX found {len(devs)}")
    if kind not in table:
        raise BenchError(f"device kind {kind!r} is not in peaks.json")
    return {"platform": platform, "kind": kind, "peaks": table[kind],
            "devices": devs[:chips]}


def memory_peak_bytes(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


# ------------------------------------------------------------------- numbers

def quantile(values, q: float) -> Optional[float]:
    """The q-quantile by linear interpolation between order statistics."""
    xs = sorted(values)
    if not xs:
        return None
    pos = q * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


@dataclasses.dataclass
class RunContext:
    """What a traced run hands to the readers of per-layer metrics."""
    cell: Cell
    dims: Any                       # reference.Dims
    peaks: dict
    chips: int
    window: tuple                   # (t0, t1) on time.perf_counter
    counters: Dict[str, float]
    spans: Dict[str, List[tuple]]   # name -> [(t0, t1)] on perf_counter
    records: List[dict]             # one dict a request (serving)
    steps: List[dict]               # one dict a decode step (serving)
    trace: Any = None               # xplane.Trace or None

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def _load_reader(name: str):
    rdir = os.path.join(BENCH_DIR, "readers")
    for p in (rdir, BENCH_DIR):     # readers import each other and harness
        if p not in sys.path:
            sys.path.insert(0, p)
    path = os.path.join(rdir, name + ".py")
    if not os.path.exists(path):
        raise BenchError(f"no reader readers/{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_reader_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_per_layer(ctx: RunContext) -> Dict[str, dict]:
    """Each per-layer metric of the cell through its reader. A reader that
    finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in ctx.cell.per_layer:
        spec = _load_json(BENCH_DIR, "metrics", m["name"] + ".json")
        value = _load_reader(spec["reader"]).read(ctx, **spec.get("params", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# -------------------------------------------------------------------- checks

@dataclasses.dataclass
class Check:
    """One number compared, beside its limit. `worse` is `above`: the number
    fails when it is over the limit."""
    name: str
    value: float
    limit: float
    where: Optional[str] = None     # the leaf a worst-leaf number is of

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


def checks_dict(checks: List[Check]) -> dict:
    return {c.name: {"value": c.value, "limit": c.limit, "ok": c.ok}
            for c in checks}


def emit(result: dict, checks: List[Check], notes: List[str]) -> None:
    """Earlier lines to stdout, the compared numbers last on stderr, then
    the result as the last line of stdout with `checks` as its last key."""
    for n in notes:
        print(n, flush=True)
    for c in checks:
        print(f"check {c.name}: {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr, flush=True)
    result = dict(result)
    result["checks"] = checks_dict(checks)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)


def now() -> float:
    return time.perf_counter()
