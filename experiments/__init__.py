"""Parity-evidence experiment harness.

Each module reproduces one of the reference's homework experiment suites at
its exact configuration, persists per-round/per-epoch curves through
``ResultSink`` CSVs under ``experiments/results/``, and prints the summary
table the reference notebook displays:

- ``hw1_fl``       — FedSGD/FedAvg N- and C-sweeps (lab/hw01/homework-1.ipynb
                     cells 27, 30).
- ``hw1b_llm``     — the 5000-iter tiny-Llama loss trajectory
                     (lab/out_b1_2.txt, lab/out_b2_*.txt).
- ``hw2_vfl``      — VFL seeds/permutations, client scaling 2→10 with the
                     even and min-2 partitioners, VFL-VAE 1000 epochs
                     (lab/hw02/Tea_Pula_HW2.ipynb cells 2-41).
- ``hw3_defenses`` — the robust-aggregation grid under 20% gradient
                     reversion + Bulyan/SparseFed sweeps
                     (lab/hw03/Tea_Pula_03.ipynb cells 3-29).
- ``generative``   — centralized heart classifier + VAE synthetic-data
                     evaluation (lab/tutorial_2a).
- ``pp_schedules`` — GPipe vs 1F1B schedule time/memory measurements.
- ``plots``        — accuracy-curve rendering from the persisted CSVs
                     (lab/hw03/Tea_Pula_03.ipynb cell 11).

``python -m experiments.run_all [--quick]`` runs the whole suite; every row
is labeled with its data provenance (real vs synthetic fallback — see
``common.data_provenance``), because this environment has no network: MNIST
and TinyStories use the in-repo synthetic fallbacks unless real files are
present, while heart.csv is the real reference data.
"""
