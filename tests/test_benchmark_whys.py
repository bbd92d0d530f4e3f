"""Each serving cell's ``why`` in BENCHMARK.json against its traffic file: the
rate, the requests, the slots and the lengths that the line states are the
file's. (PR 35 re-sized two cells and the line of one kept the old backlog
for a review: the line is what a reader of the ledger sees, so it is held to
the file here.)"""

import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
RUN_SECONDS = BENCH["run_seconds"]


def traffic(name):
    with open(os.path.join(REPO, "benchmarks", "traffic", name + ".json")) as f:
        return json.load(f)


SERVING = [w for w in BENCH["workloads"]
           if traffic(w["traffic"])["kind"].startswith("serve")]


@pytest.mark.parametrize("cell", SERVING, ids=lambda w: w["name"])
def test_the_why_states_the_traffic_files_numbers(cell):
    why, tr = cell["why"], traffic(cell["traffic"])
    assert len(why) <= 200
    # arrivals: a rate and the requests it offers in a window, or a backlog
    if tr.get("arrivals") == "backlog":
        assert re.search(rf"\b{tr['backlog_requests']}\b", why), why
        assert "rate_rps" not in tr
    else:
        assert f"{tr['rate_rps']:g}/s" in why
        offered = max(1, int(round(tr["rate_rps"] * RUN_SECONDS)))
        assert f"{offered} requests" in why
    # slots, and the positions one holds
    assert f"{tr['num_slots']} slots" in why
    of = re.search(r"slots of (\d+)", why)
    if of:
        assert int(of.group(1)) == tr["block_len"] * tr["max_blocks_per_seq"]
    # every request fits a slot, and the pool holds what the slots can
    assert (tr["prompt_len"]["max"] + tr["output_len"]["max"] - 1
            <= tr["block_len"] * tr["max_blocks_per_seq"])
    # lengths: "~median (min-max)", or another cell's mix by name
    found = 0
    for key, word in (("prompt_len", "prompts"), ("output_len", "outputs")):
        m = re.search(rf"{word} ~(\d+) \((\d+)-(\d+)\)", why)
        if m:
            found += 1
            assert [int(g) for g in m.groups()] == [
                tr[key]["median"], tr[key]["min"], tr[key]["max"]], (key, why)
    other = re.search(r"the (\w+) mix's lengths", why)
    if other:
        theirs = traffic(other.group(1))
        assert tr["prompt_len"] == theirs["prompt_len"]
        assert tr["output_len"] == theirs["output_len"]
    else:
        assert found == 2, why
    chunks = re.search(r"(\d+)-token chunks", why)
    if chunks:
        assert int(chunks.group(1)) == tr["prefill_chunk"]
    if "greedy" in why:
        assert tr["temperature"] == 0.0


def test_every_serving_cell_is_held():
    assert len(SERVING) >= 4
    assert {w["name"] for w in SERVING} >= {
        "serve_dsllm7b_chat", "serve_dsllm7b_backlog",
        "serve_axk1_docs_backlog", "serve_falconh1_chat_backlog"}
