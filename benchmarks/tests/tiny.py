"""Tiny copies of the benchmark for the CPU: the same files, small sizes."""
import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)

TINY_MODEL = dict(hidden_size=64, intermediate_size=176,
                  num_attention_heads=2, num_key_value_heads=2,
                  num_hidden_layers=2, vocab_size=512)

TINY_SERVE = dict(num_slots=4, block_len=8, max_blocks_per_seq=12,
                  num_blocks=49, prefill_chunk=16, rate_rps=20.0,
                  prompt_len={"median": 24, "sigma": 0.6, "min": 4, "max": 64},
                  output_len={"median": 8, "sigma": 0.5, "min": 2, "max": 24},
                  trace_seconds=1, checked_requests=6)


def _edit(path, **changes):
    with open(path) as f:
        data = json.load(f)
    data.update(changes)
    with open(path, "w") as f:
        json.dump(data, f)


def make_tiny_checkout(dest: str) -> str:
    """Copy BENCHMARK.json and benchmarks/ to `dest` and shrink every
    configuration and traffic file to sizes a CPU runs in seconds."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dest)
    shutil.copytree(BENCH, os.path.join(dest, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bdir = os.path.join(dest, "benchmarks")
    for name in os.listdir(os.path.join(bdir, "configs")):
        _edit(os.path.join(bdir, "configs", name), **TINY_MODEL)
    for name in os.listdir(os.path.join(bdir, "traffic")):
        path = os.path.join(bdir, "traffic", name)
        with open(path) as f:
            kind = json.load(f)["kind"]
        if kind == "train":
            _edit(path, seq_len=64, trace_seconds=1)
        elif "backlog_requests" in json.load(open(path)):
            _edit(path, **{k: v for k, v in TINY_SERVE.items()
                           if k != "rate_rps"}, backlog_requests=2000)
        else:
            _edit(path, **TINY_SERVE)
    return dest



def write_tiny_limits(dest: str) -> None:
    """Limits for the tiny sizes: bf16 against float32 at width 64 reads
    about 3e-4 (gradient norms), 5e-4 (parameter change) and a served gap
    under 0.01; ten times that separates the planted faults."""
    ldir = os.path.join(dest, "benchmarks", "limits")
    with open(os.path.join(ldir, "train_dscoder1b_seq4k.json"), "w") as f:
        json.dump({"grad_norm_gap": 3e-3, "param_change_gap": 5e-3}, f)
    for cell in ("serve_dsllm7b_chat", "serve_dsllm7b_backlog"):
        with open(os.path.join(ldir, cell + ".json"), "w") as f:
            json.dump({"served_logit_gap": 0.05}, f)
