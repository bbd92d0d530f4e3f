"""Smoke tests for the parity-evidence experiment harness.

These exercise the runners' plumbing (setup → server/trainer → ResultSink →
parity report) at tiny scale; the committed full-scale results live under
experiments/results/.
"""

import os

import numpy as np
import pytest

from ddl25spring_tpu.config import FLConfig
from ddl25spring_tpu.data import tabular


def test_dedup_split_has_no_train_test_twins():
    X, y = tabular.load_heart()
    feats, _ = tabular.preprocess(X)
    x_tr, y_tr, x_te, y_te = tabular.train_test_split(feats, y, seed=0,
                                                      dedup=True)
    train_rows = {tuple(r) + (int(t),) for r, t in zip(np.round(x_tr, 6), y_tr)}
    leaks = sum(tuple(r) + (int(t),) in train_rows
                for r, t in zip(np.round(x_te, 6), y_te))
    assert leaks == 0
    assert len(y_te) > 0 and len(y_tr) > 0
    # the plain split on the REAL (duplicate-expanded) dataset DOES leak —
    # that is the point of the dedup variant; the synthetic fallback draws
    # unique random rows, so only assert this against real data
    from experiments import common
    if common.heart_provenance() == "heart-real":
        x_tr2, y_tr2, x_te2, y_te2 = tabular.train_test_split(feats, y, seed=0)
        train_rows2 = {tuple(r) + (int(t),)
                       for r, t in zip(np.round(x_tr2, 6), y_tr2)}
        leaks2 = sum(tuple(r) + (int(t),) in train_rows2
                     for r, t in zip(np.round(x_te2, 6), y_te2))
        assert leaks2 > 0


def test_hw1_run_one_writes_provenance_rows(tmp_path):
    from ddl25spring_tpu.fl import FedAvgServer
    from ddl25spring_tpu.utils.tracing import ResultSink

    from experiments import hw1_fl

    sink = ResultSink(str(tmp_path / "out.csv"))
    cfg = FLConfig(nr_clients=4, client_fraction=0.5, batch_size=20,
                   rounds=2, seed=10)
    acc = hw1_fl.run_one(FedAvgServer, cfg, sink, "mnist-synthetic",
                         n_train=200, n_test=50)
    assert 0.0 <= acc <= 1.0
    df = sink.read_df()
    assert len(df) == 2 and set(df["data"]) == {"mnist-synthetic"}
    assert list(df["round"]) == [1, 2]


def test_hw3_defense_hooks_resolve():
    from experiments.hw3_defenses import _defense_hook

    assert _defense_hook("none", 2) is None
    for name, extra in (("krum", {}), ("multi_krum", {}),
                        ("majority_sign", {}),
                        ("bulyan", {"k": 4, "beta": 0.2}),
                        ("sparse_fed", {"topk_fraction": 0.4})):
        assert callable(_defense_hook(name, 2, **extra))
    with pytest.raises(ValueError):
        _defense_hook("unknown", 2)


def test_complete_bulyan_partial_cell_drop(tmp_path, monkeypatch):
    """The resume path must treat a truncated cell as missing: drop its
    rows and re-run it whole, and never re-run a complete cell."""
    import pandas as pd

    from experiments import common, hw3_defenses

    monkeypatch.setattr(common, "RESULTS_DIR", str(tmp_path))
    rows = []
    for k, beta, n in [(10, 0.2, 3), (14, 0.4, 1)]:  # complete vs partial
        for r in range(1, n + 1):
            rows.append(dict(k=k, beta=beta, round=r, test_accuracy=0.1 * r,
                             n_train=100, n_test=50))
    rows = pd.DataFrame(rows)
    # make k=10/0.2 complete at rounds=3, leave k=14/0.4 partial
    path = tmp_path / "hw3_bulyan.csv"
    rows.to_csv(path, index=False)

    ran = []
    monkeypatch.setattr(
        hw3_defenses, "run_one",
        lambda defense, iid, sink, prov, **kw: ran.append(
            (kw["extra"]["k"], kw["extra"]["beta"])) or 0.5)
    hw3_defenses.complete_bulyan(rounds=3)
    # complete cell skipped, partial cell re-run, all other grid cells run
    assert (10, 0.2) not in ran
    assert (14, 0.4) in ran
    assert len(ran) == 8
    left = pd.read_csv(path)
    assert len(left[(left["k"] == 14) & (left["beta"] == 0.4)]) == 0


def test_hw1b_configs_cover_reference_topologies():
    from experiments.hw1b_llm import CONFIGS

    assert CONFIGS["pp3"] == dict(data=1, stage=3, microbatches=3)
    assert CONFIGS["dp2_pp3"] == dict(data=2, stage=3, microbatches=3)


def test_parity_report_renders_from_committed_results():
    from experiments import parity_report

    text = parity_report.render()
    assert "# PARITY" in text
    assert "hw1" in text and "hw2" in text and "hw3" in text
    # provenance discipline: the report explains the synthetic fallbacks
    assert "synthetic" in text.lower()


def test_provenance_labels():
    from experiments import common

    assert common.mnist_provenance() in ("mnist-real", "mnist-synthetic")
    assert common.heart_provenance() in ("heart-real", "heart-synthetic")
    assert common.tinystories_provenance() in (
        "tinystories-real", "tinystories-synthetic")


def test_hw3_backdoor_run_one_records_clean_and_asr(tmp_path):
    """The backdoor runner's per-round record carries both metrics and the
    protocol metadata (experiments/hw3_backdoor.py)."""
    from unittest import mock

    from ddl25spring_tpu.utils.tracing import ResultSink

    from experiments import hw3_backdoor

    sink = ResultSink(str(tmp_path / "bkd.csv"))
    small = dict(hw3_backdoor.HW3, nr_clients=10, client_fraction=0.4,
                 batch_size=20, epochs=1)
    with mock.patch.dict(hw3_backdoor.HW3, small, clear=True):
        res = hw3_backdoor.run_one("median", sink, "mnist-synthetic",
                                   rounds=2, n_train=200, n_test=80)
    assert 0.0 <= res["clean"] <= 1.0 and 0.0 <= res["asr"] <= 1.0
    df = sink.read_df()
    assert len(df) == 2
    assert {"clean_accuracy", "backdoor_asr", "defense", "round"} <= set(df.columns)
    assert set(df["defense"]) == {"median"}


def test_vfl_faithful_freezes_bottoms():
    """The dominant reference quirk (train/vfl.py): with train_bottoms=False
    the bottom models' parameters are bit-identical after training while the
    top still learns."""
    import jax
    import jax.numpy as jnp

    from ddl25spring_tpu.config import VFLConfig
    from ddl25spring_tpu.models import vfl_nets
    from ddl25spring_tpu.train.vfl import train_vfl

    rng = np.random.default_rng(0)
    xs = [rng.normal(size=(80, d)).astype(np.float32) for d in (3, 4)]
    y = rng.integers(0, 2, 80)
    init = vfl_nets.init_vfl(jax.random.key(7), [3, 4])
    cfg = VFLConfig(nr_clients=2, epochs=3, batch_size=20, seed=7)
    params, _ = train_vfl(xs, y, xs, y, cfg, train_bottoms=False)
    for a, b in zip(jax.tree.leaves(init["bottoms"]),
                    jax.tree.leaves(params["bottoms"])):
        assert jnp.array_equal(a, b)
    moved = [not jnp.array_equal(a, b)
             for a, b in zip(jax.tree.leaves(init["top"]),
                             jax.tree.leaves(params["top"]))]
    assert all(moved)


def test_bench_compare_direction_aware_gating(tmp_path):
    """bench_compare judges wire_bytes_* rows lower-is-better: a candidate
    ABOVE the best (lowest) committed row regresses, one below improves —
    while throughput rows keep their higher-is-better direction (the
    satellite fix: a wire-bytes regression must gate, not pass as an
    'improvement')."""
    import json

    from experiments.bench_compare import compare, lower_is_better

    assert lower_is_better("wire_bytes_per_train_step")
    assert lower_is_better("payload_bytes_per_step")
    assert not lower_is_better("tiny_llama_train_tokens_per_sec_per_chip")
    # ISSUE 19 direction pin: the bucketed backward's overlap window is
    # higher-is-better — a SHRINKING overlap_fraction is the regression.
    assert not lower_is_better("overlap_fraction")

    def row(metric, value):
        return json.dumps({"metric": metric, "value": value,
                           "platform": "cpu", "variant": "v"})

    committed = str(tmp_path / "base_r01.json")
    with open(committed, "w") as f:
        f.write(row("wire_bytes_per_train_step", 100.0) + "\n"
                + row("tps", 1000.0) + "\n")

    # Wire bytes UP 100% -> regression; throughput up is never one.
    worse = str(tmp_path / "cand_worse.json")
    with open(worse, "w") as f:
        f.write(row("wire_bytes_per_train_step", 200.0) + "\n"
                + row("tps", 2000.0) + "\n")
    _, regressions = compare([committed], worse, 20.0)
    assert len(regressions) == 1
    assert "wire_bytes_per_train_step" in regressions[0]
    assert "above best" in regressions[0]

    # Wire bytes DOWN is the improvement the lever exists for.
    better = str(tmp_path / "cand_better.json")
    with open(better, "w") as f:
        f.write(row("wire_bytes_per_train_step", 25.0) + "\n")
    _, regressions = compare([committed], better, 20.0)
    assert regressions == []

    # Throughput still gates downward.
    slow = str(tmp_path / "cand_slow.json")
    with open(slow, "w") as f:
        f.write(row("tps", 100.0) + "\n")
    _, regressions = compare([committed], slow, 20.0)
    assert len(regressions) == 1 and "below best" in regressions[0]

    # overlap_fraction gates downward too: a shrinking overlap window
    # (first hop waiting on more of the backward) is the regression.
    committed2 = str(tmp_path / "base_r02.json")
    with open(committed2, "w") as f:
        f.write(row("overlap_fraction", 0.8) + "\n")
    shrunk = str(tmp_path / "cand_shrunk.json")
    with open(shrunk, "w") as f:
        f.write(row("overlap_fraction", 0.4) + "\n")
    _, regressions = compare([committed2], shrunk, 20.0)
    assert len(regressions) == 1 and "below best" in regressions[0]
    grown = str(tmp_path / "cand_grown.json")
    with open(grown, "w") as f:
        f.write(row("overlap_fraction", 0.9) + "\n")
    _, regressions = compare([committed2], grown, 20.0)
    assert regressions == []
