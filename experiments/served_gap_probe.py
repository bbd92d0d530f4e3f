"""Where a `serve_latent_experts` cell's `served_logit_gap` comes from (a TPU
or nothing; PERF.md, PR 33, has what was read). Two commands, each over a
checkout `<root>` (this tree, or the parent unpacked with this tree's
`benchmarks/` laid over it); files go to `chiprun_out/` of the directory the
command is started in:

    python experiments/served_gap_probe.py dump <root> <tag> [--like <npz>] \\
        --workload <cell> --seed <n> --seconds <s> --trace 0

is `benchmarks/run.py` with the checked requests kept: `dump_<tag>_<seed>.npz`
holds each one's rid, prompt, served tokens and the gap at every served
position. With `--like`, the requests checked are those of that dump (found
by their prompts) and not the run's own draw, finished or not: two engines
of other speeds are then read on the same requests.

    python experiments/served_gap_probe.py force <root> <tag> \\
        --workload <cell> --seed <n> <npz>:<i> [<npz>:<i> ...]

builds the cell's engine from the seed, alone in its slots, and for request
`<i>` of each dump runs `prefill_chunk` over the prompt and `decode_step`
over the SERVED tokens (each step is fed the dump's token, whatever the
step itself chose), then puts what the steps chose through the reference:
what `<root>`'s programs read on another run's continuation. Prints one
JSON line a request and keeps `force_<tag>_<seed>_<k>.npz`.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

OUT = os.path.abspath("chiprun_out")


def enter(root: str) -> None:
    """`<root>`'s benchmark and program ahead of every other on the path."""
    root = os.path.abspath(root)
    os.chdir(root)
    sys.path[:0] = [os.path.join(root, "benchmarks"), root]
    os.makedirs(OUT, exist_ok=True)


def padded(prompt, served, pad_to: int) -> np.ndarray:
    toks = np.zeros(pad_to, np.int32)
    toks[:len(prompt)] = prompt
    toks[len(prompt):len(prompt) + len(served)] = served
    return toks


def dump(root: str, tag: str, like: str | None, argv: list) -> int:
    like = like and os.path.abspath(like)
    enter(root)
    import runpy

    import serve_latent_experts_cell as cellmod
    import traffic_gen
    from ddl25spring_tpu.serving import scheduler
    from references import latent_experts as ref

    kept = {}
    plain_offered = traffic_gen.offered
    plain_init = scheduler.Scheduler.__init__

    def offered(*a, **kw):
        kept["offered"] = plain_offered(*a, **kw)
        return kept["offered"]

    def init(self, *a, **kw):       # the window's scheduler is the last built
        plain_init(self, *a, **kw)
        kept["records"] = self.records

    def served_gaps(seed32, dims, samples, pad_to, control=False):
        import jax.numpy as jnp

        rid_of = {np.asarray(o.prompt, np.int32).tobytes(): o.rid
                  for o in kept["offered"]}
        if like is not None:
            with np.load(like) as d:
                prompts = [d[k] for k in sorted(d.files)
                           if k.startswith("prompt")]
            samples = [(p, np.asarray(
                kept["records"][rid_of[p.tobytes()]].tokens, np.int32))
                for p in prompts]
            samples = [(p, s) for p, s in samples if len(s)]
        model = ref.Seeded(seed32, dims, "bfloat16")
        out, keep = [], {}
        for i, (prompt, served) in enumerate(samples):
            n = len(prompt) + len(served)
            toks = jnp.asarray(padded(prompt, served, pad_to))
            gaps = np.asarray(ref.gap_below_best(model, toks, toks[1:]))[
                len(prompt) - 1: n - 1]
            out.append(float(gaps.max()))
            rid = rid_of[np.asarray(prompt, np.int32).tobytes()]
            keep.update({f"rid{i}": np.asarray(rid), f"prompt{i}": prompt,
                         f"served{i}": served, f"gaps{i}": gaps,
                         f"finished{i}": np.asarray(
                             kept["records"][rid].done_t is not None)})
        np.savez(os.path.join(OUT, f"dump_{tag}_{seed32}.npz"), **keep)
        return out

    traffic_gen.offered = offered
    scheduler.Scheduler.__init__ = init
    cellmod.served_gaps = served_gaps
    sys.argv = ["run.py"] + argv
    runpy.run_path(os.path.join("benchmarks", "run.py"), run_name="__main__")
    return 0


def force(root: str, tag: str, workload: str, seed: int, picks: list) -> int:
    picks = [(os.path.abspath(p.rsplit(":", 1)[0]), int(p.rsplit(":", 1)[1]))
             for p in picks]
    enter(root)
    import jax
    import jax.numpy as jnp

    import harness
    import serve_latent_experts_cell as cellmod
    from ddl25spring_tpu.serving.engine import Engine
    from ddl25spring_tpu.serving.kvcache import PagedKVConfig
    from references import latent_experts as ref

    cell = harness.load_cell(workload)
    harness.device_info(cell.chips)
    harness.enable_compile_cache()
    tr, dims = cell.traffic, ref.Dims.from_config(cell.config)
    mcfg, seed32 = cellmod.model_config(cell, dims), seed % (2 ** 32)
    paged = PagedKVConfig(num_blocks=tr["num_blocks"],
                          block_len=tr["block_len"],
                          max_blocks_per_seq=tr["max_blocks_per_seq"],
                          kv_dtype=cell.config["cache_dtype"])
    params = jax.block_until_ready(
        ref.make_weights(seed32, dims, mcfg.param_dtype))
    engine = Engine(params, mcfg, paged, tr["num_slots"],
                    prefill_chunk=tr["prefill_chunk"])
    del params
    runs, now = [], {}

    def fed_back(advance):
        """`advance`'s events kept, and the dump's token put where the next
        step reads the one it is to follow."""
        def wrapped(*a):
            events = advance(*a)
            for ev in events:
                now["chose"].append(ev.token)
                if not ev.done:
                    engine.last_tok[ev.slot] = now["fed"][
                        len(now["chose"]) - 1]
            return events
        return wrapped

    engine._advance_prefill = fed_back(engine._advance_prefill)
    engine._advance_decode = fed_back(engine._advance_decode)
    for path, i in picks:
        with np.load(path) as d:
            prompt, fed = d[f"prompt{i}"], d[f"served{i}"]
            fed_gaps = d[f"gaps{i}"]
        now.update(fed=fed, chose=[])
        engine.admit(prompt, len(fed), temperature=float(tr["temperature"]))
        while engine.busy:
            engine.step()
        runs.append((path, i, prompt, fed, fed_gaps,
                     np.asarray(now["chose"], np.int32)))
    del engine
    model = ref.Seeded(seed32, dims, "bfloat16")
    for k, (path, i, prompt, fed, fed_gaps, chose) in enumerate(runs):
        n = len(prompt) + len(fed)
        toks = padded(prompt, fed, paged.max_seq_len)
        chosen = toks[1:].copy()
        chosen[len(prompt) - 1: n - 1] = chose
        gaps = np.asarray(ref.gap_below_best(
            model, jnp.asarray(toks), jnp.asarray(chosen)))[
                len(prompt) - 1: n - 1]
        other = np.nonzero(chose != fed)[0]
        worst = int(np.argmax(gaps))
        print(json.dumps({
            "root": root, "seed": seed, "fed": f"{os.path.basename(path)}:{i}",
            "prompt_len": len(prompt), "served": len(fed),
            "positions_chosen_otherwise": other.tolist(),
            "gap_of_choice_there": [float(gaps[j]) for j in other],
            "gap_of_fed_token_there": [float(fed_gaps[j]) for j in other],
            "widest_gap_of_choices": float(gaps[worst]), "at": worst,
            "choice_is_fed_token_there": bool(chose[worst] == fed[worst]),
            "widest_gap_of_fed_tokens": float(fed_gaps.max()),
            "fed_at": int(np.argmax(fed_gaps))}), flush=True)
        np.savez(os.path.join(OUT, f"force_{tag}_{seed32}_{k}.npz"),
                 prompt=prompt, fed=fed, chose=chose, gaps=gaps,
                 fed_gaps=fed_gaps)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("command", choices=("dump", "force"))
    ap.add_argument("root")
    ap.add_argument("tag")
    ap.add_argument("--like")
    args, rest = ap.parse_known_args()
    if args.command == "dump":
        return dump(args.root, args.tag, args.like, rest)
    fp = argparse.ArgumentParser()
    fp.add_argument("--workload", required=True)
    fp.add_argument("--seed", type=int, required=True)
    fp.add_argument("picks", nargs="+")
    f = fp.parse_args(rest)
    return force(args.root, args.tag, f.workload, f.seed, f.picks)


if __name__ == "__main__":
    sys.exit(main())
