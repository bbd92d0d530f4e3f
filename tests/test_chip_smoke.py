"""``chip_smoke.py`` away from the chip: it rehearses, and it never succeeds.

The script's success line is reserved for a TPU. Here, pinned to the CPU, a
run without arguments must fail, and ``--rehearse`` must go through every
phase at tiny sizes and still end on a line that is not the success line.
Each run is a process of its own, as the driver starts it.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(*args):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=600)
    lines = [json.loads(line) for line in proc.stdout.splitlines()]
    return proc, lines


def test_no_tpu_no_success_line():
    proc, lines = _run()
    assert proc.returncode != 0
    assert lines[-1]["ok"] is False
    assert lines[-1]["device"]["platform"] == "cpu"
    assert [line for line in lines if "phase" in line] == [], \
        "no phase may run before the platform check"


def test_rehearsal_runs_every_phase_and_is_never_ok():
    proc, lines = _run("--rehearse")
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert lines[-1] == {"ok": False, "rehearsal": "passed",
                         "device": {"platform": "cpu", "kind": "cpu",
                                    "count": 1}}
    phases = {line["phase"]: line for line in lines if "phase" in line}
    assert list(phases) == ["start", "kernels", "train", "serve"]
    for line in phases.values():
        assert (line["platform"], line["device_kind"],
                line["device_count"]) == ("cpu", "cpu", 1)
    # The rehearsal interprets the kernels and says so; the trainer's
    # manifest says which attention path the step was built with.
    assert phases["kernels"]["interpret"] is True
    for run in phases["train"]["runs"].values():
        assert run["attention"] == {"impl": "xla", "interpret": None}
        assert [leg["compilations_after_warmup"] for leg in run["legs"]] \
            == [0, 0]
        assert run["legs"][1]["start_step"] == 8
    assert phases["start"]["compile_cache_dir"] == os.environ.get(
        "JAX_COMPILATION_CACHE_DIR", os.path.join(REPO, ".jax_cache"))
