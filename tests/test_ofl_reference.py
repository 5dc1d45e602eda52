"""OCC online facility location against the benchmark's plain reference
(`chipbench/reference_ofl.py`, which imports nothing of the program), at
DEEP's width on the CPU: the streamed engine decides every send, opening
and assignment as serial OFL does in the Thm 3.1 order, for chunkings that
cut epochs apart; the compiled state draw gives `point_uniforms`' bits; and
the `deep96-ofl.train` cell's check (`chipbench/algorithms/ofl.py`) passes
the program's answer, and fails each planted fault and the bfloat16
control, at the cell's own limits.
"""
from __future__ import annotations

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "chipbench")
if BENCH not in sys.path:
    sys.path.append(BENCH)

import common  # noqa: E402
import reference_ofl  # noqa: E402
import traffic  # noqa: E402

from repro.core import OCCEngine, OFLTransaction  # noqa: E402
from repro.core.ofl import _draw_uniforms, point_uniforms  # noqa: E402

CELL = "deep96-ofl.train"
D, PB, N, LAM, K_MAX = 96, 128, 3000, 1.6, 4096
KEY = 20260416
CHUNKINGS = (100, 333, 1000)         # none divides PB: epochs span calls


def _points(seed=7):
    return common.mixture(seed, N, 64, D, 0.8)[1]


def _stream(x, chunk):
    """The engine's answer for x arriving in chunks, then `flush`."""
    eng = OCCEngine(OFLTransaction(LAM, K_MAX, common.seed_key(KEY)), pb=PB,
                    validate_cap="adaptive")
    parts = [eng.partial_fit(x[lo:lo + chunk])
             for lo in range(0, x.shape[0], chunk)]
    parts.append(eng.flush())
    parts = [p for p in parts if p is not None]
    assign = np.concatenate([np.asarray(p.assign) for p in parts])
    send = np.concatenate([np.asarray(p.send) for p in parts])
    return assign, send, eng.pool


@pytest.fixture(scope="module")
def serial():
    x = _points()
    u = reference_ofl.uniforms(common.seed_key(KEY), N)
    return x, reference_ofl.serial_ofl(x, u, LAM, PB)


@pytest.mark.parametrize("chunk", CHUNKINGS)
def test_streamed_engine_is_serial_ofl(serial, chunk):
    x, (assign, send, facilities) = serial
    got_a, got_s, pool = _stream(x, chunk)
    k = facilities.shape[0]
    assert 0.1 * N < k < 0.5 * N, k          # about a quarter open
    assert 0 < send.sum() < N
    np.testing.assert_array_equal(got_s, send)
    np.testing.assert_array_equal(got_a, assign)
    assert int(pool.count) == k and not bool(pool.overflow)
    centers = np.asarray(pool.centers)
    np.testing.assert_array_equal(centers[:k], np.asarray(facilities))
    assert not centers[k:].any()


@pytest.mark.parametrize("n,offset", [(1, 0), (N, 0), (777, 2 ** 21 - 777),
                                      (2 ** 17, 2 ** 20)])
def test_compiled_uniforms_are_point_uniforms(n, offset):
    key = common.seed_key(KEY)
    want = np.asarray(point_uniforms(key, n, offset)).view(np.uint32)
    got = np.asarray(_draw_uniforms(key, offset, n=n)).view(np.uint32)
    ref = np.asarray(reference_ofl.uniforms(key, n, offset)).view(np.uint32)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(ref, want)


def test_compiled_uniforms_compile_once_per_length():
    key = common.seed_key(KEY)
    before = _draw_uniforms._cache_size()
    for offset in (0, 4096, 8192, 123456):
        _draw_uniforms(key, offset, n=4096)
    assert _draw_uniforms._cache_size() - before <= 1


# ------------------------------------------------- the cell's own check

def _cell():
    spec = common.benchmark_spec()
    cell = common.find_cell(spec, CELL)
    cfg = common.config_of(spec, cell)
    algo = common.algorithm(cfg)
    mix = dict(common.mix_of(cell), chunk_points=2048, check_block=256,
               _cell=CELL)
    limits = common.load_json(
        os.path.join(BENCH, "limits", CELL + ".json"))["limits"]
    return algo.small(cfg), mix, algo, limits


def _job(seed=11):
    cfg, mix, algo, _ = _cell()
    x = traffic.job_data(cfg, mix, seed)
    (assigns, sends, pool), _ = traffic._one_job(
        x, cfg, mix, None, algo.transaction(cfg, seed))
    return x, (jnp.concatenate(assigns), jnp.concatenate(sends), pool)


def _within(readings, limits):
    return all(readings[k] <= limits[k] for k in readings)


def test_check_passes_the_program():
    cfg, mix, algo, limits = _cell()
    x, answer = _job()
    got = algo.check(x, answer, cfg, mix)
    assert got["answers_bad"] == 0 and got["assign_gap"] == 0.0, got
    assert _within(got, limits), (got, limits)
    assert cfg["dim"] == D and 0.1 < int(answer[2].count) / x.shape[0] < 0.5


def test_check_fails_the_bfloat16_control():
    cfg, mix, algo, limits = _cell()
    x, answer = _job()
    got = algo.control(x, answer, cfg, mix, jnp.bfloat16)
    assert not _within(got, limits), (got, limits)


@pytest.mark.parametrize("fault", range(3))
def test_check_fails_each_planted_fault(fault, monkeypatch):
    cfg, mix, algo, limits = _cell()
    assert len(algo.FAULTS) == 3
    algo.FAULTS[fault](monkeypatch)
    x, answer = _job()
    got = algo.check(x, answer, cfg, mix)
    assert not _within(got, limits), (algo.FAULTS[fault].__name__, got)


def test_configuration_states_its_guarantee_and_cut():
    spec = common.benchmark_spec()
    cfg = common.config_of(spec, common.find_cell(spec, CELL))
    assert (cfg["algorithm"], cfg["dim"], cfg["pb"], cfg["k_max"]) == (
        "ofl", D, 256, 1 << 20)
    assert cfg["reduced"] == [] and "serial equivalence" in json.dumps(
        cfg["guarantees"])
