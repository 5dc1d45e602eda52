"""Every training cell reads, at its small size, what it read before its
algorithm moved into a file of its own (`chipbench/algorithms/dp_means.py`):
the numbers `correct` compares, the jobs attempted and failed, K and the
epochs, on two seeds, equal those recorded from the tree before the move.
A window of 0 s holds one job, so every number is fixed by the seed.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest chipbench/tests
"""
from __future__ import annotations

import pytest

import test_correct as tc

SEEDS = (2 ** 31 + 4321, 2 ** 33 + 977)

# Recorded on the tree before the move with the same runs (`readings`).
RECORDED = {
    ("deep96.train", SEEDS[0]): {
        "correct": True, "attempted": 1, "failed": 0,
        "k": 920, "epochs": 64,
        "checks": {"answers_bad": 0, "assign_gap": 0.0,
                   "rule_gap": -1.1563301086425781e-05, "jobs_differing": 0}},
    ("deep96.train", SEEDS[1]): {
        "correct": True, "attempted": 1, "failed": 0,
        "k": 920, "epochs": 64,
        "checks": {"answers_bad": 0, "assign_gap": 0.0,
                   "rule_gap": -1.1682510375976562e-05, "jobs_differing": 0}},
    ("laion512.train", SEEDS[0]): {
        "correct": True, "attempted": 1, "failed": 0,
        "k": 380, "epochs": 64,
        "checks": {"answers_bad": 0, "assign_gap": 0.0,
                   "rule_gap": -1.1205673217773438e-05, "jobs_differing": 0}},
    ("laion512.train", SEEDS[1]): {
        "correct": True, "attempted": 1, "failed": 0,
        "k": 380, "epochs": 64,
        "checks": {"answers_bad": 0, "assign_gap": 0.0,
                   "rule_gap": -1.1324882507324219e-05, "jobs_differing": 0}},
    ("laion512.train.4chip", SEEDS[0]): {
        "correct": True, "attempted": 1, "failed": 0,
        "k": 380, "epochs": 64,
        "checks": {"answers_bad": 0, "assign_gap": 0.0,
                   "rule_gap": -1.1205673217773438e-05, "jobs_differing": 0}},
    ("laion512.train.4chip", SEEDS[1]): {
        "correct": True, "attempted": 1, "failed": 0,
        "k": 380, "epochs": 64,
        "checks": {"answers_bad": 0, "assign_gap": 0.0,
                   "rule_gap": -1.1324882507324219e-05, "jobs_differing": 0}},
}


def readings(cell: str, seed: int) -> dict:
    out, counters = tc.run_counted(cell, seed)
    return {"correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"], "k": counters["k_final"],
            "epochs": counters["epochs"],
            "checks": {n: v["value"] for n, v in out["checks"].items()}}


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("cell", tc.TRAIN_CELLS + tc.MESH_CELLS)
def test_a_cell_reads_as_before_the_move(cell, seed):
    if cell in tc.MESH_CELLS:
        got = tc._mesh_run("readings", str(seed))
    else:
        got = readings(cell, seed)
    assert got == RECORDED[cell, seed]
