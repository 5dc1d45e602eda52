"""A configuration names its OCC algorithm, and the harness finds the file
by that name (`chipbench/algorithms/<algorithm>.py`): a configuration that
names none, or a file that is not there, fails at once with the path; every
algorithm file gives what the harness calls; and algorithms the
benchmark has no cell of yet run through `run_cell` from test-only files
(`tests/algorithms/`), with no change to the generator.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest chipbench/tests
"""
from __future__ import annotations

import glob
import json
import os
import re

import pytest

import test_correct as tc  # puts chipbench/ on the path

import common  # noqa: E402
import run  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
TEST_ALGORITHMS = os.path.join(HERE, "algorithms")
CELL = "deep96.train"
CONTRACT = ("transaction", "check", "control", "propose_work",
            "PROPOSE_KERNEL", "small", "FAULTS")


def _spec_with_config(tmp_path, **change) -> dict:
    """The benchmark with `CELL`'s configuration file replaced by a copy
    under tmp_path, changed as `change` says (None drops a key)."""
    spec = json.loads(json.dumps(tc.SPEC))
    entry = next(c for c in spec["configs"]
                 if c["name"] == common.find_cell(spec, CELL)["config"])
    cfg = common.load_json(os.path.join(common.ROOT, entry["file"]))
    for k, v in change.items():
        if v is None:
            del cfg[k]
        else:
            cfg[k] = v
    path = tmp_path / "config.json"
    path.write_text(json.dumps(cfg))
    entry["file"] = str(path)
    return spec


def test_a_configuration_without_an_algorithm_fails_with_its_path(tmp_path):
    spec = _spec_with_config(tmp_path, algorithm=None)
    path = str(tmp_path / "config.json")
    with pytest.raises(SystemExit, match=re.escape(path)):
        common.config_of(spec, common.find_cell(spec, CELL))
    with pytest.raises(SystemExit, match=re.escape(path)):
        run.run_cell(spec, CELL, tc.SEED, 0.0, False, devices=tc.devices)


def test_an_algorithm_file_that_is_not_there_fails_with_its_path(tmp_path):
    spec = _spec_with_config(tmp_path, algorithm="no_such_algorithm")
    path = os.path.join(common.ALGORITHMS, "no_such_algorithm.py")
    with pytest.raises(SystemExit, match=re.escape(path)):
        run.run_cell(spec, CELL, tc.SEED, 0.0, False, devices=tc.devices)
    path = os.path.join(TEST_ALGORITHMS, "no_such_algorithm.py")
    with pytest.raises(SystemExit, match=re.escape(path)):
        common.algorithm({"algorithm": "no_such_algorithm"}, TEST_ALGORITHMS)


@pytest.mark.parametrize("path", sorted(
    glob.glob(os.path.join(common.ALGORITHMS, "*.py"))))
def test_every_algorithm_file_gives_what_the_harness_calls(path):
    name = os.path.basename(path)[:-3]
    algo = common.algorithm({"algorithm": name})
    missing = [k for k in CONTRACT if not hasattr(algo, k)]
    assert not missing, (path, missing)
    assert algo.PROPOSE_KERNEL is None or isinstance(algo.PROPOSE_KERNEL, str)
    assert algo.FAULTS


@pytest.mark.parametrize("name", ["ofl", "bp_means"])
def test_an_algorithm_without_a_cell_runs_through_the_jobs_generator(name):
    """The whole run of a cell on the CPU with its configuration naming a
    test-only algorithm file: the jobs generator builds that file's
    transaction, keeps the answer as `partial_fit` returns it ((N,) int32
    facilities for OFL, (N, K_max) bool features for BP-means), and the
    file's structural check reads it well formed.  For OFL the seed keys
    the uniforms: two seeds open different numbers of facilities."""
    algo = common.algorithm({"algorithm": name}, TEST_ALGORITHMS)
    cfg = algo.small(dict(common.config_of(tc.SPEC,
                                           common.find_cell(tc.SPEC, CELL)),
                          algorithm=name))
    limits = {"answers_bad": 0, "jobs_differing": 0}
    ks = []
    for seed in (tc.SEED, tc.SEED + 1):
        out, counters = tc.run_counted(CELL, seed, config=cfg, limits=limits,
                                       algorithms=TEST_ALGORITHMS)
        assert out["correct"], out["checks"]
        assert out["checks"]["answers_bad"]["value"] == 0
        assert 1 < counters["k_final"] < cfg["k_max"], counters["k_final"]
        assert counters["epochs"] == -(-cfg["n_points"] // cfg["pb"])
        ks.append(counters["k_final"])
    if name == "ofl":
        assert ks[0] != ks[1], ks
