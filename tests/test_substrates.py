"""Substrate units: optimizer, compression, checkpoint, elastic, fault,
data pipeline, curation."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.data.curation import curate
from repro.data.synthetic import dp_stick_breaking_data
from repro.data.tokens import TokenPipeline
from repro.distributed.elastic import plan_shrunk_mesh, build_mesh_from_plan
from repro.distributed.fault import HeartbeatTracker, StepWatchdog
from repro.optim.adamw import (adamw_init, adamw_update, clip_by_global_norm,
                               cosine_lr, global_norm)
from repro.optim.compression import (apply_error_feedback, compress_int8,
                                     decompress_int8, ef_init)


# ---------------------------------------------------------------- optimizer

def test_adamw_converges_quadratic():
    params = {"w": jnp.asarray([5.0, -3.0, 2.0])}
    state = adamw_init(params)
    target = jnp.asarray([1.0, 2.0, -1.0])
    for step in range(300):
        grads = {"w": params["w"] - target}
        params, state = adamw_update(params, grads, state, lr=0.05,
                                     weight_decay=0.0)
    np.testing.assert_allclose(np.asarray(params["w"]), np.asarray(target),
                               atol=0.05)


def test_cosine_lr_schedule():
    assert float(cosine_lr(0, 1.0, warmup=10, total=100)) == pytest.approx(0.1)
    assert float(cosine_lr(9, 1.0, warmup=10, total=100)) == pytest.approx(1.0)
    assert float(cosine_lr(99, 1.0, warmup=10, total=100)) <= 0.15


def test_clip_global_norm():
    g = {"a": jnp.ones(4) * 10.0}
    clipped, gn = clip_by_global_norm(g, 1.0)
    assert float(gn) == pytest.approx(20.0)
    assert float(global_norm(clipped)) == pytest.approx(1.0, rel=1e-5)


# -------------------------------------------------------------- compression

def test_int8_roundtrip_error_bounded():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(256,)).astype(np.float32))
    q, s = compress_int8(x)
    err = np.abs(np.asarray(decompress_int8(q, s)) - np.asarray(x))
    assert err.max() <= float(s) * 0.5 + 1e-7


def test_error_feedback_telescopes():
    """With EF, the *cumulative* applied update tracks the cumulative true
    gradient: residual stays bounded, bias telescopes to zero."""
    rng = np.random.default_rng(1)
    grads_seq = [{"w": jnp.asarray(rng.normal(size=(64,)).astype(np.float32))}
                 for _ in range(50)]
    ef = ef_init(grads_seq[0])
    applied = jnp.zeros(64)
    true = jnp.zeros(64)
    for g in grads_seq:
        dec, ef = apply_error_feedback(g, ef)
        applied = applied + dec["w"]
        true = true + g["w"]
    resid = np.asarray(ef.residual["w"])
    np.testing.assert_allclose(np.asarray(applied + resid), np.asarray(true),
                               rtol=1e-4, atol=1e-4)
    assert np.abs(resid).max() < 0.1   # bounded by one quantization step


# --------------------------------------------------------------- checkpoint

def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {"a": jnp.asarray(rng.normal(size=(4, 3)).astype(np.float32)),
            "nested": {"b": jnp.arange(5, dtype=jnp.int32)}}


def test_checkpoint_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    t = _tree()
    mgr.save(10, t)
    step, restored = mgr.restore(jax.eval_shape(lambda: t))
    assert step == 10
    np.testing.assert_array_equal(np.asarray(restored["a"]), np.asarray(t["a"]))
    np.testing.assert_array_equal(np.asarray(restored["nested"]["b"]),
                                  np.asarray(t["nested"]["b"]))


def test_checkpoint_keep_k_and_latest(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, _tree(s))
    assert mgr.all_steps() == [3, 4]
    assert mgr.latest_step() == 4


def test_checkpoint_atomic_no_tmp_left(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, _tree())
    assert not any(d.endswith(".tmp") for d in os.listdir(tmp_path))


def test_checkpoint_async(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_write=True)
    mgr.save(5, _tree())
    mgr.wait()
    assert mgr.latest_step() == 5


def test_checkpoint_missing_leaf_raises(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, {"a": jnp.zeros(3)})
    with pytest.raises(KeyError):
        mgr.restore({"a": jnp.zeros(3), "extra": jnp.zeros(2)})


# ------------------------------------------------------------------ elastic

def test_elastic_plan_shrinks_data_axis():
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))

    class FakeMesh:
        shape = {"pod": 2, "data": 16, "model": 16}
    plan = plan_shrunk_mesh(FakeMesh(), n_failed=3)
    # 3 failures with 32 devices per data rank -> lose 1 data rank
    assert plan.new_shape == {"pod": 2, "data": 15, "model": 16}
    plan0 = plan_shrunk_mesh(FakeMesh(), n_failed=0)
    assert plan0.new_shape["data"] == 16


def test_elastic_too_many_failures():
    class FakeMesh:
        shape = {"data": 2, "model": 2}
    with pytest.raises(RuntimeError):
        plan_shrunk_mesh(FakeMesh(), n_failed=4)


# -------------------------------------------------------------------- fault

def test_watchdog_flags_straggler():
    wd = StepWatchdog(threshold=2.0, warmup_steps=2)
    events = [wd.observe(i, 1.0) for i in range(8)]
    assert all(e is None for e in events)
    ev = wd.observe(9, 5.0)
    assert ev is not None and ev.ratio > 2.0
    # outlier not folded into ewma
    assert wd.ewma == pytest.approx(1.0, rel=0.05)


def test_heartbeat_dead_hosts():
    hb = HeartbeatTracker(timeout=10.0)
    hb.beat(0, now=0.0)
    hb.beat(1, now=5.0)
    assert hb.dead_hosts(now=12.0) == [0]


# --------------------------------------------------------------------- data

def test_token_pipeline_deterministic_and_restartable():
    p = TokenPipeline(1000, global_batch=4, seq_len=8, seed=3)
    b1 = p.batch_at(7)
    b2 = p.batch_at(7)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    np.testing.assert_array_equal(b1["tokens"][:, 1:], b1["labels"][:, :-1])
    assert b1["tokens"].max() < 1000


def test_token_pipeline_host_sharding():
    full = TokenPipeline(100, 4, 8, seed=0)
    h0 = TokenPipeline(100, 4, 8, seed=0, host_index=0, host_count=2)
    h1 = TokenPipeline(100, 4, 8, seed=0, host_index=1, host_count=2)
    assert h0.host_batch == 2 and h1.host_batch == 2
    assert not np.array_equal(h0.batch_at(0)["tokens"],
                              h1.batch_at(0)["tokens"])


def test_curation_downweights_duplicates():
    x, z, _ = dp_stick_breaking_data(512, seed=0)
    # inject near-duplicates
    x[:100] = x[0] + 0.01 * np.random.default_rng(0).normal(size=(100, 16))
    rep = curate(jnp.asarray(x), lam=4.0, pb=64, k_max=128)
    assert rep.n_clusters >= 1
    assert rep.keep_weight.min() < 1.0       # the duplicate cluster got capped
    assert rep.keep_weight.max() <= 1.0


# ------------------------------------------------------ compilation cache

@pytest.mark.parametrize("env_dir", [None, "/var/cache/jax-programs"])
def test_compile_cache_directory(monkeypatch, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins untouched; otherwise the cache sits at
    the fixed, git-ignored <repo>/.jax_cache."""
    from repro.launch.compile_cache import REPO_CACHE_DIR, use_compile_cache
    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    try:
        got = use_compile_cache()
        if env_dir is None:
            repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            assert got == os.path.join(repo, ".jax_cache") == str(REPO_CACHE_DIR)
            assert jax.config.jax_compilation_cache_dir == got
            with open(os.path.join(repo, ".gitignore")) as f:
                assert ".jax_cache/" in f.read().split()
        else:
            assert got == env_dir
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
